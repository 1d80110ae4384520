"""The reference makes each layer alone and gets the served model's values."""
import jax
import numpy as np

import reference
import shapes
import traffic
from conftest import BENCH
from weights import LAYER_STREAM, key, layer_weights, program_params

TINY = traffic.load(BENCH / "tests" / "data" / "tiny.json")


def test_one_layer_alone_equals_its_slice_of_the_whole_model():
    s = shapes.Sizes.from_config(TINY)
    w = TINY["weights"]
    whole = jax.jit(lambda: program_params(3, s, w))()
    for l in range(s.layers):
        one = layer_weights(key(3, LAYER_STREAM, l), s, w)
        np.testing.assert_array_equal(whole["blocks"]["q"]["w"][l], one["q"])
        np.testing.assert_array_equal(whole["blocks"]["mlp"]["w_down"]["w"][l],
                                      one["down"])
        # float32 leaves may differ in the last bit between fusions
        np.testing.assert_allclose(whole["blocks"]["k"]["b"][l], one["k_b"],
                                   rtol=1e-6)


def test_dequant_walks_the_ladder_by_hand():
    # one column, amax 127: the INT8 scale is 1, so the codes are w
    w = np.array([[127.0], [64.0], [-3.0], [1.0], [2.0]], np.float32)
    at = lambda bits, rung: np.asarray(reference.dequant(w, bits, rung))[:, 0]
    np.testing.assert_allclose(at((4, 6, 8), 2), [127, 64, -3, 1, 2])
    # INT6: round(c / 4), 31.75 clipped to 31, 0.5 to even 0; times 4
    np.testing.assert_allclose(at((4, 6, 8), 1), [124, 64, -4, 0, 0])
    # INT4 from the INT6 codes [31, 16, -1, 0, 0]: 7.75 clipped to 7; x16
    np.testing.assert_allclose(at((4, 6, 8), 0), [112, 64, 0, 0, 0])
    # a ladder of one width is plain round-to-nearest: scale 127 / 7
    np.testing.assert_allclose(at((4,), 0), [127, 4 * 127 / 7, 0, 0, 0],
                               rtol=1e-6)

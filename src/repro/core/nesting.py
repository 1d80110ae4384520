"""NestQuant procedures (paper Algorithm 1 + Eq. 12 selection rule),
generalized to a K-rung nesting ladder (DESIGN.md Sec. 8).

``nest_quantize`` runs the layer-wise procedure on one weight matrix:
  step 1  INT-n Hessian-based (SQuant-style) quantization of w
  step 2  recursively, per adjacent ladder pair (b_hi > b_lo): INT-b_lo
          Hessian-based quantization of the current codes / 2^gap, plus
          the (gap+1)-bit compensated delta (paper Eq. 11 applied per
          level) - the paper's single split is the 2-rung special case
  step 3  pack the base-bit codes and every delta stream (packed-bit
          tensors, the kernels' blocked layout)

``nest_quantize_tree`` applies it over a model parameter pytree, nesting
every matmul weight (>= 2D, both trailing dims >= min_dim) and keeping
norms / biases / tiny tensors in floating point - mirroring the paper,
which nests layer weights and keeps scales in FP32.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import packing
from .decompose import (chain_decompose, chain_recompose, delta_bits,
                        ladder_gaps, normalize_bits, recompose, split_high)
from .quantizer import compute_scale, dequantize, int_range
from .squant import adaptive_round


# ---------------------------------------------------------------------------
# Nested tensor container (a pytree so it can live inside model params)
# ---------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclass
class NestedTensor:
    """Packed NestQuant ladder representation of one weight tensor.

    The logical weight has shape ``shape`` = (..., K, N); quantization is
    per-output-channel (axis N), the SQuant flip group is the reduction
    axis K.  ``w_base`` holds packed bits[0]-bit base codes and
    ``deltas[i]`` the packed (gap_i+1)-bit compensated delta that upgrades
    rung i to rung i+1 (paper Eq. 11 per level), all BLOCK-packed along K
    (core.packing.pack_blocked with ``block`` elements per block) - the
    layout the Pallas packed/nested/ladder matmul kernels stream directly,
    so serving never materializes a dense weight.  The paper's two-level
    nesting is the ``bits=(h, n)`` special case with one delta stream.

    ``rung`` is static metadata stamped by the switching store: it selects
    how many packed streams (base + deltas[:rung]) the model-side matmul
    dispatch reads.  The arrays themselves are identical at every rung -
    a rung switch is a pure residency/metadata flip.

    Delta entries may be ``None``: a NON-RESIDENT stream whose bytes live
    in a :class:`~repro.storage.pager.DeltaPager` (DESIGN.md Sec. 10).
    Residency is always a prefix (levels 0..r-1 present); the stamped
    ``rung`` never exceeds it, and all byte accounting is computed from
    (shape, bits, block) metadata so paged-out leaves account exactly.
    """
    w_base: jax.Array             # packed int32, (..., K/block*blocked_rows(block,bits[0]), N)
    deltas: Tuple[jax.Array, ...]  # packed int32 delta streams, ascending
    scale: jax.Array              # f32, (..., 1, N) - the TOP-rung scale
    shape: Tuple[int, ...]        # logical shape
    bits: Tuple[int, ...]         # ascending rung bitwidths, e.g. (4, 6, 8)
    block: int = packing.DEFAULT_BLOCK   # pack block along K (= kernel block_k)
    rung: int = -1                       # resident/serving rung (-1 = top)

    def __post_init__(self):
        self.bits = tuple(self.bits)
        self.deltas = tuple(self.deltas)
        self.rung = check_rung(self.rung, len(self.bits))

    # -- pytree plumbing ----------------------------------------------------
    def tree_flatten(self):
        return ((self.w_base,) + tuple(self.deltas) + (self.scale,),
                (self.shape, self.bits, self.block, self.rung))

    @classmethod
    def tree_unflatten(cls, aux, children):
        shape, bits, block, rung = aux
        w_base, deltas, scale = children[0], children[1:-1], children[-1]
        return cls(w_base, tuple(deltas), scale, shape, bits, block, rung)

    # -- rung metadata -------------------------------------------------------
    @property
    def num_rungs(self) -> int:
        return len(self.bits)

    @property
    def top(self) -> int:
        return len(self.bits) - 1

    def with_rung(self, rung: int) -> "NestedTensor":
        rung = check_rung(rung, self.num_rungs)
        if rung == self.rung:
            return self
        return NestedTensor(self.w_base, self.deltas, self.scale, self.shape,
                            self.bits, self.block, rung)

    def with_mode(self, mode: str) -> "NestedTensor":
        """Two-level-era alias: 'full' = top rung, 'part' = base rung."""
        return self.with_rung(mode_to_rung(mode, self.num_rungs))

    # -- partial residency (delta streams owned by a pager) -----------------
    @property
    def resident_levels(self) -> int:
        """Leading delta streams actually present (residency is a prefix:
        a store pages levels in and out one adjacent rung at a time)."""
        n = 0
        for d in self.deltas:
            if d is None:
                break
            n += 1
        return n

    def with_deltas(self, deltas) -> "NestedTensor":
        """Copy with a new delta tuple (page-in/out by the store).  The
        stamped rung is clamped to the new residency so the matmul
        dispatch can never be pointed at a paged-out stream."""
        nt = NestedTensor(self.w_base, tuple(deltas), self.scale, self.shape,
                          self.bits, self.block, self.rung)
        return nt.with_rung(min(nt.rung, nt.resident_levels))

    @property
    def mode(self) -> str:
        return rung_to_mode(self.rung, self.num_rungs)

    # -- derived ------------------------------------------------------------
    @property
    def n(self) -> int:
        """Full (top-rung) bitwidth."""
        return self.bits[-1]

    @property
    def h(self) -> int:
        """Base (always-resident) bitwidth - the paper's nested part."""
        return self.bits[0]

    @property
    def l(self) -> int:
        return self.n - self.h

    @property
    def gaps(self) -> Tuple[int, ...]:
        return ladder_gaps(self.bits)

    @property
    def K(self) -> int:
        return self.shape[-2]

    @property
    def w_high(self) -> jax.Array:
        """Two-level-era alias for the packed base stream."""
        return self.w_base

    @property
    def w_low(self) -> jax.Array:
        """Two-level-era alias: the single delta stream of a 2-rung tensor."""
        assert len(self.deltas) == 1, \
            f"w_low is ambiguous on a {self.num_rungs}-rung ladder"
        return self.deltas[0]

    def rung_scale(self, rung: int) -> jax.Array:
        """Per-rung dequant scale s * 2^(n - bits[rung]) (Eq. 10 per rung)."""
        return self.scale * (2.0 ** (self.bits[-1] - self.bits[rung]))

    @property
    def part_scale(self) -> jax.Array:
        """Inflated part-bit scale s * 2^l (Eq. 10) - the one definition
        shared by the dense, gather, and kernel part-bit paths."""
        return self.rung_scale(0)

    # -- byte accounting -----------------------------------------------------
    # Computed from (shape, bits, block) METADATA, never from the arrays:
    # identical to the packed array sizes (asserted in tests), and exact
    # even for streams currently paged out to a DeltaPager (deltas[i] is
    # None) or for abstract ShapeDtypeStruct trees.
    def _rest(self) -> int:
        """Elements per K-slice: every dim except the packing axis K."""
        r = 1
        for d in self.shape[:-2] + self.shape[-1:]:
            r *= int(d)
        return r

    def _stream_rows(self, width: int) -> int:
        """int32 word rows of one width-bit stream (K padded to blocks)."""
        return math.ceil(self.K / self.block) * \
            packing.blocked_rows(self.block, width)

    def nbytes_base(self) -> int:
        return self._stream_rows(self.bits[0]) * self._rest() * 4

    def nbytes_delta(self, i: int) -> int:
        return self._stream_rows(delta_bits(self.bits)[i]) * self._rest() * 4

    def stream_nbytes(self) -> Tuple[int, ...]:
        """Per-stream packed bytes: (base, delta_0, ..., delta_{R-2})."""
        return (self.nbytes_base(),) + tuple(
            self.nbytes_delta(i) for i in range(len(self.deltas)))

    def nbytes_high(self) -> int:
        return self.nbytes_base()

    def nbytes_low(self) -> int:
        """Bytes above the base: ALL delta streams together."""
        return sum(self.nbytes_delta(i) for i in range(len(self.deltas)))

    def nbytes_scales(self) -> int:
        return self._rest() * 4                     # f32 (..., 1, N)

    # -- materialization ----------------------------------------------------
    def codes_base(self) -> jax.Array:
        return packing.unpack_blocked(self.w_base, self.bits[0], self.K,
                                      self.block, axis=self.w_base.ndim - 2)

    def codes_delta(self, i: int) -> jax.Array:
        if self.deltas[i] is None:
            raise ValueError(
                f"delta stream {i} is not resident (paged out to the "
                "store's pager); fetch it via NestQuantStore before use")
        width = delta_bits(self.bits)[i]
        return packing.unpack_blocked(self.deltas[i], width, self.K,
                                      self.block, axis=self.deltas[i].ndim - 2)

    def codes_at(self, rung: int) -> jax.Array:
        """INT-bits[rung] codes: climb the ladder from the base (Eq. 6 per
        resident delta) - exact at every rung by per-level compensation."""
        rung = check_rung(rung, self.num_rungs)
        return chain_recompose(self.codes_base(),
                               [self.codes_delta(i) for i in range(rung)],
                               self.bits, rung)

    def codes_high(self) -> jax.Array:
        return self.codes_base()

    def codes_low(self) -> jax.Array:
        assert len(self.deltas) == 1, \
            f"codes_low is ambiguous on a {self.num_rungs}-rung ladder"
        return self.codes_delta(0)

    def codes_full(self) -> jax.Array:
        return self.codes_at(self.top)

    def rung_weight(self, rung: int, dtype=jnp.bfloat16) -> jax.Array:
        """Dequantized rung-``rung`` weight: s * 2^(n-b_r) * codes_at(r).

        (No reshape: unpack restores the logical trailing dims, and leading
        stacked dims may have been sliced away by a layer scan.)"""
        rung = check_rung(rung, self.num_rungs)
        return dequantize(self.codes_at(rung), self.rung_scale(rung), dtype)

    def part_bit(self, dtype=jnp.bfloat16) -> jax.Array:
        """Dequantized base-rung weight: s * 2^l * w_base (Eq. 10)."""
        return self.rung_weight(0, dtype)

    def full_bit(self, dtype=jnp.bfloat16) -> jax.Array:
        """Dequantized full-bit weight after page-in + recompose."""
        return self.rung_weight(self.top, dtype)

    def dequant(self, dtype=jnp.bfloat16) -> jax.Array:
        """Dequantize according to the stamped serving ``rung``."""
        return self.rung_weight(self.rung, dtype)

    def gather_rows(self, idx: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
        """Dequantized logical rows ``idx`` along the packed K axis, read
        straight from the packed words (the embedding-gather path: only the
        word rows covering the requested tokens are touched, never the
        whole table).  Returns (*idx.shape, N) in ``dtype``, honouring
        ``rung``."""
        assert self.w_base.ndim == 2, "row gather expects a 2-D weight"
        flat = idx.reshape(-1)
        widths = delta_bits(self.bits)
        codes = packing.gather_block_rows(self.w_base, self.bits[0],
                                          self.block, flat)
        for i in range(self.rung):
            d = packing.gather_block_rows(self.deltas[i], widths[i],
                                          self.block, flat)
            codes = recompose(codes, d, self.bits[i + 1], self.bits[i])
        scale = self.rung_scale(self.rung)
        out = dequantize(codes, scale, dtype)        # scale (1, N) broadcasts
        return out.reshape(tuple(idx.shape) + (self.shape[-1],))


def check_rung(rung: int, num_rungs: int) -> int:
    """Validate a rung index (python-style negatives allowed: -1 = top).

    Out-of-range indices RAISE instead of wrapping - silently serving a
    different operating point than requested would corrupt ledger and
    quality accounting."""
    if not -num_rungs <= rung < num_rungs:
        raise ValueError(
            f"rung {rung} out of range for a {num_rungs}-rung ladder")
    return rung % num_rungs


def mode_to_rung(mode, num_rungs: int) -> int:
    """'part' -> 0, 'full' -> top, 'rungK' -> K, ints pass through."""
    if isinstance(mode, int):
        return check_rung(mode, num_rungs)
    if mode == "full":
        return num_rungs - 1
    if mode == "part":
        return 0
    if isinstance(mode, str) and mode.startswith("rung"):
        return check_rung(int(mode[4:]), num_rungs)
    raise ValueError(f"unknown mode {mode!r}")


def rung_to_mode(rung: int, num_rungs: int) -> str:
    if rung == num_rungs - 1:
        return "full"
    if rung == 0:
        return "part"
    return f"rung{rung}"


# ---------------------------------------------------------------------------
# Eq. 12: critical nested combination rule of thumb
# ---------------------------------------------------------------------------
def critical_nested_bits(model_size_mb: float, n: int = 8) -> int:
    if model_size_mb < 3e1:
        return n // 2 + 1
    if model_size_mb < 3e2:
        return n // 2
    return n // 2 - 1


# ---------------------------------------------------------------------------
# Algorithm 1 on a single (K, N) (or batched (..., K, N)) weight
# ---------------------------------------------------------------------------
# Largest (K, N) piece quantized or dequantized at once.  A whole
# full-width layer stack or vocabulary table at once needs more
# temporaries (the sort buffers of adaptive rounding, the unpacked
# streams) than one chip holds.  Layers and columns share no scale, CASE
# flip group or packed word, so pieces give the same codes and weights.
PIECE_ELEMS = 1 << 24


def _pieces(shape):
    """Cut a (..., K, N) tensor into independent pieces: one per leading
    index, and blocks of columns for a 2-D tensor of more than
    PIECE_ELEMS elements.  Returns ([(index, piece shape)], join), where
    ``join`` puts the per-piece results back together, or None when the
    tensor is one piece."""
    if len(shape) > 2:
        return [(i, shape[1:]) for i in range(shape[0])], jnp.stack
    K, N = shape
    cols = max(PIECE_ELEMS // K, 1)
    if N <= cols:
        return None
    return ([((slice(None), slice(j, j + cols)), (K, min(cols, N - j)))
             for j in range(0, N, cols)],
            partial(jnp.concatenate, axis=-1))


def _split_level(cur: jax.Array, b_hi: int, b_lo: int, rounding: str,
                 group_size: Optional[int]) -> jax.Array:
    """INT-b_lo quantization of INT-b_hi codes / 2^gap (one ladder level).

    For 'adaptive' the CASE flip group is the reduction axis K (axis -2 of
    the weight), hence the swapaxes dance; other roundings go through
    decompose.split_high."""
    gap = b_hi - b_lo
    if rounding == "adaptive":
        vt = jnp.swapaxes(cur.astype(jnp.float32) / (2 ** gap), -1, -2)
        lo, hi = int_range(b_lo)
        return jnp.swapaxes(
            jnp.clip(adaptive_round(vt, b_lo, group_size=group_size), lo, hi),
            -1, -2).astype(jnp.int32)
    return split_high(cur, b_hi, b_lo, method=rounding)


def nest_quantize(w: jax.Array, n: int = 8, h: Optional[int] = None,
                  rounding: str = "adaptive",
                  group_size: Optional[int] = None,
                  block: Optional[int] = None,
                  bits: Optional[Sequence[int]] = None,
                  validate: bool = True) -> NestedTensor:
    """Algorithm 1, ladder-generalized.  ``bits`` (any order, e.g.
    ``(8, 6, 4)``) selects the rung chain; when omitted the paper's
    two-level ``(n, h)`` nesting is used (``h=None`` -> Eq. 12).

    ``validate`` (default ON) asserts the exactness invariant at every
    ladder split - codes in the {floor, ceil} pair of their targets and
    bit-exact recomposition (DESIGN.md Sec. 13); it is a no-op under jit
    tracing and costs one eager pass per level otherwise."""
    assert w.ndim >= 2, "nest_quantize expects a matmul weight (..., K, N)"
    if bits is None:
        if h is None:
            h = critical_nested_bits(w.size * 4 / 1e6, n)
        bits = (h, n)
    bits = normalize_bits(bits)
    n = bits[-1]
    if block is None:
        block = packing.choose_block(w.shape[-2])
    shape = tuple(w.shape)
    split = _pieces(shape)
    if split is not None:
        pieces, join = split
        parts = [nest_quantize(w[ix], rounding=rounding, group_size=group_size,
                               block=block, bits=bits, validate=validate)
                 for ix, _ in pieces]
        return NestedTensor(
            w_base=join([p.w_base for p in parts]),
            deltas=tuple(join(ds) for ds in zip(*(p.deltas for p in parts))),
            scale=join([p.scale for p in parts]),
            shape=shape, bits=bits, block=block)
    w = w.astype(jnp.float32)

    # step 1: INT-n quantization, per-output-channel scale (reduced over the
    # K axis), CASE flips over K.
    qmax = 2 ** (n - 1) - 1
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / qmax
    v = w / scale
    del w
    if rounding == "adaptive":
        # flip group = reduction axis K
        w_int = jnp.swapaxes(adaptive_round(jnp.swapaxes(v, -1, -2), n,
                                            group_size=group_size), -1, -2)
    else:
        lo, hi = int_range(n)
        w_int = jnp.clip(jnp.round(v), lo, hi).astype(jnp.int32)
    del v

    # step 2: walk the ladder top-down: at each adjacent pair quantize the
    # current codes to the lower bitwidth with the chosen rounding and keep
    # the (gap+1)-bit compensated delta (Eq. 11 per level, exact).
    cur, deltas = chain_decompose(
        w_int, bits,
        split_fn=lambda c, b_hi, b_lo: _split_level(c, b_hi, b_lo,
                                                    rounding, group_size),
        validate=validate)

    # step 3: block-pack the base codes and every delta stream along K -
    # the layout the Pallas packed/nested/ladder matmul kernels consume.
    ax = len(shape) - 2
    widths = delta_bits(bits)
    return NestedTensor(
        w_base=packing.pack_blocked(cur, bits[0], block, axis=ax),
        deltas=tuple(packing.pack_blocked(d, widths[i], block, axis=ax)
                     for i, d in enumerate(deltas)),
        scale=scale,
        shape=shape,
        bits=bits,
        block=block,
    )


# ---------------------------------------------------------------------------
# Whole-model nesting
# ---------------------------------------------------------------------------
def default_predicate(path: str, leaf: Any, min_dim: int = 64) -> bool:
    """Nest matmul weights; keep norms/bias/SSM-scalars/conv in FP."""
    if not hasattr(leaf, "ndim") or leaf.ndim < 2:
        return False
    if leaf.shape[-1] < min_dim or leaf.shape[-2] < min_dim:
        return False
    if not jnp.issubdtype(leaf.dtype, jnp.floating):
        return False
    lowered = path.lower()
    for kw in ("norm", "bias", "conv", "a_log", "router"):
        if kw in lowered:
            return False
    return True


def nest_quantize_tree(params, n: int = 8, h: Optional[int] = None,
                       rounding: str = "adaptive",
                       predicate: Callable[[str, Any], bool] = default_predicate,
                       group_size: Optional[int] = None,
                       block: Optional[int] = None,
                       bits: Optional[Sequence[int]] = None):
    """Apply Algorithm 1 across a parameter pytree.

    DEPRECATED keyword-soup shim: build a declarative
    :class:`repro.core.recipe.QuantRecipe` and call
    ``repro.api.quantize(params, recipe)`` instead - recipes add ordered
    per-layer overrides (different ladders for attention vs MLP, dense
    embeddings, ...) that this entry point cannot express.

    ``bits`` selects a K-rung ladder (e.g. ``(8, 6, 4)``); otherwise
    ``h=None`` selects the critical nested combination per-model via
    Eq. 12 (model size in MB).
    """
    import warnings

    from .recipe import QuantRecipe, quantize
    warnings.warn(
        "nest_quantize_tree is a compatibility shim; prefer "
        "repro.api.quantize(params, QuantRecipe(...)) (DESIGN.md Sec. 9)",
        DeprecationWarning, stacklevel=2)
    if bits is None:
        if h is None:
            size_mb = sum(
                x.size * 4 / 1e6 for x in jax.tree_util.tree_leaves(params)
                if hasattr(x, "size")
            )
            h = critical_nested_bits(size_mb, n)
        bits = (h, n)
    recipe = QuantRecipe(bits=normalize_bits(bits), rounding=rounding,
                         block=block, group_size=group_size,
                         predicate=predicate)
    return quantize(params, recipe)


def materialize(nested_params, mode: str = "full", dtype=jnp.bfloat16):
    """Dequantize a nested pytree to dense weights, piece by piece
    (see PIECE_ELEMS).

    ``mode``: 'full' | 'part' | 'rungK' | an int rung index."""
    def dense(x, rung):
        split = _pieces(x.shape)
        if split is None:
            return x.rung_weight(rung, dtype)
        pieces, join = split
        return join([dense(NestedTensor(
            x.w_base[ix], tuple(d if d is None else d[ix] for d in x.deltas),
            x.scale[ix], shp, x.bits, x.block, x.rung), rung)
            for ix, shp in pieces])

    def leaf_fn(x):
        if isinstance(x, NestedTensor):
            return dense(x, mode_to_rung(mode, x.num_rungs))
        return x
    return jax.tree_util.tree_map(
        leaf_fn, nested_params, is_leaf=lambda x: isinstance(x, NestedTensor))


def set_tree_rung(nested_params, rung):
    """Stamp the serving rung on every NestedTensor leaf.

    ``rung`` is either an int (uniform stamp, clamped to each leaf's own
    ladder top - per-layer recipes yield trees whose leaves have
    different depths) or a mapping ``{keystr path: rung}`` for per-leaf
    assignments (DESIGN.md Sec. 9); unmapped leaves keep their stamp.
    O(#leaves) metadata flip - no array touches, no dequantization.  The
    model-side matmul dispatch reads the stamp to pick the packed
    stream(s)."""
    if isinstance(rung, int):
        r = check_rung(rung, tree_num_rungs(nested_params))
        return jax.tree_util.tree_map(
            lambda x: (x.with_rung(min(r, x.top))
                       if isinstance(x, NestedTensor) else x),
            nested_params, is_leaf=lambda x: isinstance(x, NestedTensor))
    # map form: same contract as the int form - validate against the
    # TREE depth (so tree-level rungs and negatives are accepted), then
    # clamp to each leaf's own ladder top
    depth = tree_num_rungs(nested_params)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        nested_params, is_leaf=lambda x: isinstance(x, NestedTensor))
    out = []
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        if isinstance(leaf, NestedTensor) and key in rung:
            leaf = leaf.with_rung(min(check_rung(rung[key], depth), leaf.top))
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def set_tree_mode(nested_params, mode: str):
    """Two-level-era alias of :func:`set_tree_rung` ('full' | 'part')."""
    return jax.tree_util.tree_map(
        lambda x: x.with_mode(mode) if isinstance(x, NestedTensor) else x,
        nested_params, is_leaf=lambda x: isinstance(x, NestedTensor))


def tree_num_rungs(nested_params) -> int:
    """Ladder depth of a nested pytree (max over NestedTensor leaves; 1
    when the tree holds no nested leaf)."""
    depth = 1
    for leaf in jax.tree_util.tree_leaves(
            nested_params, is_leaf=lambda x: isinstance(x, NestedTensor)):
        if isinstance(leaf, NestedTensor):
            depth = max(depth, leaf.num_rungs)
    return depth


def tree_bytes(nested_params) -> Dict[str, int]:
    """Byte accounting over a nested pytree (packed sizes + FP leftovers).

    'high' is the always-resident base stream, 'low' every delta stream
    together (== the single w_low for two-level nesting)."""
    acc = {"high": 0, "low": 0, "scales": 0, "fp": 0}
    for leaf in jax.tree_util.tree_leaves(
            nested_params, is_leaf=lambda x: isinstance(x, NestedTensor)):
        if isinstance(leaf, NestedTensor):
            acc["high"] += leaf.nbytes_high()
            acc["low"] += leaf.nbytes_low()
            acc["scales"] += leaf.nbytes_scales()
        elif hasattr(leaf, "nbytes"):
            acc["fp"] += int(leaf.nbytes)
    acc["total"] = sum(acc.values())
    return acc


def tree_ladder_bytes(nested_params) -> Dict[str, Any]:
    """Per-rung byte accounting: {'base', 'deltas': [bytes(delta_0), ...],
    'scales', 'fp', 'total'}.  ``deltas[i]`` is exactly what an upgrade
    from rung i to rung i+1 pages in (the Table-11 ledger, K-rung)."""
    depth = tree_num_rungs(nested_params)
    acc = {"base": 0, "deltas": [0] * max(depth - 1, 0), "scales": 0, "fp": 0}
    for leaf in jax.tree_util.tree_leaves(
            nested_params, is_leaf=lambda x: isinstance(x, NestedTensor)):
        if isinstance(leaf, NestedTensor):
            acc["base"] += leaf.nbytes_base()
            for i in range(len(leaf.deltas)):
                acc["deltas"][i] += leaf.nbytes_delta(i)
            acc["scales"] += leaf.nbytes_scales()
        elif hasattr(leaf, "nbytes"):
            acc["fp"] += int(leaf.nbytes)
    acc["total"] = acc["base"] + sum(acc["deltas"]) + acc["scales"] + acc["fp"]
    return acc

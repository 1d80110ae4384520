"""The dense decoder of Qwen2 and Mistral: RMSNorm, grouped-query
attention with RoPE, a SwiGLU MLP.  A configuration file without an
``"arch"`` key is this architecture.

Each function of the seam (``arch/__init__.py``) is what the benchmark
measured this decoder with before there was a seam: the sizes and the
yardstick of ``shapes.py``, the weights of ``weights.py`` and the
reference of ``reference.py``.
"""
from __future__ import annotations

import dataclasses
import sys
from typing import Dict

import reference
import shapes
import weights

PROGRAM_EPS = 1e-6       # built into the program's RMSNorm; not a setting

sizes = shapes.Sizes.from_config
quantized_leaves = shapes.Sizes.quantized_leaves
program_params = weights.program_params
score = reference.score
decode_step_least_s = shapes.decode_step_least_s
decode_flops = shapes.decode_flops


def program_config(config: Dict):
    """The program's model config for a configuration file: its family
    from ``program_arch``, every size from the file."""
    from repro.configs import get_config
    s = sizes(config)
    pc = dataclasses.replace(
        get_config(config["program_arch"]), num_layers=s.layers,
        d_model=s.d, d_ff=s.ff, num_heads=s.heads, num_kv_heads=s.kv_heads,
        head_dim=s.head_dim, vocab_size=s.vocab, rope_theta=s.rope_theta,
        qkv_bias=s.qkv_bias, tie_embeddings=False)
    have = (pc.family, pc.act, pc.norm, pc.compute_dtype)
    want = ("dense", "swiglu", "rmsnorm", config["torch_dtype"])
    if have != want:
        raise SystemExit(f"the program serves {have}; the configuration "
                         f"file states {want}")
    if s.eps != PROGRAM_EPS:
        print(f"[config] the program's RMSNorm eps is {PROGRAM_EPS:g}; the "
              f"configuration states {s.eps:g}, which the reference uses",
              file=sys.stderr, flush=True)
    return pc


def recipe(config: Dict):
    """Every matmul weight, the embedding and the LM head on the
    configuration's ladder, with its rounding."""
    from repro.api import QuantRecipe
    return QuantRecipe(bits=tuple(config["quant_bits"]),
                       rounding=config["quant_rounding"])

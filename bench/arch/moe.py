"""A mixture-of-experts decoder with window and full attention layers.

Mellum2-style: RMSNorm before attention and experts, grouped-query
attention with rotary embeddings, sliding-window layers with plain RoPE
and full layers with YaRN RoPE in a repeating pattern, and in every
layer a router over all experts (softmax, top k, renormalised) that
sends each token to k SwiGLU experts.  The served model is one chip's
share of an expert-parallel deployment: every layer's attention, the
embedding and the LM head, and ``num_experts`` of the published
experts (``deployment_share``).  What the other chips' experts add is
left out, in the program and in the reference alike.

The yardstick splits a decode step in two.  ``decode_step_least_s``
counts the matmuls that the packed, nested and ladder kernels compute
(attention and the LM head), as the dense decoder's does;
``expert_step_least_s`` counts the grouped expert matmuls: every held
expert's packed streams at the rung and its scales, once a step, and
the rows the router is expected to send them (M * top_k * held /
experts).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import moe_reference
import moe_weights
import shapes

KINDS = {"sliding_attention": "sliding", "full_attention": "full"}
PROGRAM_EPS = 1e-6       # built into the program's RMSNorm; not a setting


def _program_arch(config: Dict):
    """The program's model config that the file names, or SystemExit
    when the program has none (an older program than this file)."""
    try:
        from repro.configs import get_config
        pc = get_config(config["program_arch"])
    except (ImportError, KeyError) as e:
        raise SystemExit(f"the program has no configuration "
                         f"{config['program_arch']!r}: {e}")
    if not hasattr(pc, "experts_held"):
        raise SystemExit("the program's expert layer is not told which "
                         "experts it holds")
    return pc


@dataclass(frozen=True)
class Sizes:
    """The shape-bearing numbers of a configuration file."""
    d: int
    ff: int                     # expert width
    heads: int
    kv_heads: int
    head_dim: int
    layers: int
    vocab: int
    eps: float
    rope_theta: float           # sliding layers, plain RoPE
    full_theta: float
    yarn: Tuple                 # (factor, original, beta_fast, beta_slow,
                                #  attention factor) of full layers, or ()
    window: int
    layer_types: Tuple[str, ...]   # "sliding" or "full", one per layer
    experts: int                # published, all of which the router scores
    held: int                   # experts this chip holds
    first: int                  # the first of them
    top_k: int
    tied: bool
    qkv_bias: bool = False

    @classmethod
    def from_config(cls, c: Dict) -> "Sizes":
        rp = c["rope_parameters"]
        full, sliding = rp["full_attention"], rp["sliding_attention"]
        yarn = ()
        if full.get("rope_type") == "yarn":
            yarn = (float(full["factor"]),
                    int(full["original_max_position_embeddings"]),
                    float(full["beta_fast"]), float(full["beta_slow"]),
                    float(full["attention_factor"]))
        share = c["deployment_share"]
        return cls(d=c["hidden_size"], ff=c["moe_intermediate_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], layers=c["num_hidden_layers"],
                   vocab=c["vocab_size"], eps=float(c["rms_norm_eps"]),
                   rope_theta=float(sliding["rope_theta"]),
                   full_theta=float(full["rope_theta"]), yarn=yarn,
                   window=int(c["sliding_window"]),
                   layer_types=tuple(KINDS[t] for t in c["layer_types"]),
                   experts=share["experts_published"],
                   held=c["num_experts"], first=share["first_expert"],
                   top_k=c["num_experts_per_tok"],
                   tied=bool(c["tie_word_embeddings"]),
                   qkv_bias=bool(c["attention_bias"]))

    def attention_matmuls(self) -> List[Tuple[str, int, int]]:
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return [("q", self.d, q), ("k", self.d, kv), ("v", self.d, kv),
                ("o", q, self.d)]

    def expert_matmuls(self) -> List[Tuple[str, int, int]]:
        return [("gate", self.d, self.ff), ("up", self.d, self.ff),
                ("down", self.ff, self.d)]


def sizes(config: Dict) -> Sizes:
    """The file's sizes, once the program is known to serve it."""
    _program_arch(config)
    if set(config["mlp_layer_types"]) != {"sparse"}:
        raise SystemExit("every layer of this architecture is sparse; the "
                         f"file states {sorted(set(config['mlp_layer_types']))}")
    return Sizes.from_config(config)


def program_config(config: Dict):
    """The program's model config for a configuration file: its family
    from ``program_arch``, every size and this chip's share of experts
    from the file."""
    s = sizes(config)
    factor, original, fast, slow, attn = s.yarn or (0.0, 0, 32.0, 1.0, 1.0)
    pc = dataclasses.replace(
        _program_arch(config), num_layers=s.layers, d_model=s.d, d_ff=s.ff,
        num_heads=s.heads, num_kv_heads=s.kv_heads, head_dim=s.head_dim,
        vocab_size=s.vocab, num_experts=s.experts, top_k=s.top_k,
        expert_first=s.first, experts_held=s.held, qkv_bias=s.qkv_bias,
        tie_embeddings=False, rope_theta=s.rope_theta,
        layer_types=s.layer_types, sliding_window=s.window,
        yarn_factor=factor, yarn_original_max_len=original,
        yarn_beta_fast=fast, yarn_beta_slow=slow,
        yarn_attention_factor=attn)
    have = (pc.family, pc.act, pc.norm, pc.compute_dtype, s.full_theta,
            s.eps, bool(config["norm_topk_prob"]))
    want = ("moe", "swiglu", "rmsnorm", config["torch_dtype"], s.rope_theta,
            PROGRAM_EPS, True)
    if have != want:
        raise SystemExit(f"the program serves {have}; the configuration "
                         f"file states {want}")
    return pc


def recipe(config: Dict):
    """Every matmul weight (the expert stacks among them), the embedding
    and the LM head on the configuration's ladder; the router stays
    float32."""
    from repro.api import QuantRecipe
    return QuantRecipe(bits=tuple(config["quant_bits"]),
                       rounding=config["quant_rounding"])


program_params = moe_weights.program_params
score = moe_reference.score


def quantized_leaves(s: Sizes) -> List[Tuple[int, int, int]]:
    """(K, N, count): attention per layer, each held expert's three
    matmuls per layer, the embedding (V, d) and the LM head (d, V)."""
    out = [(K, N, s.layers) for _, K, N in s.attention_matmuls()]
    out += [(K, N, s.layers * s.held) for _, K, N in s.expert_matmuls()]
    return out + [(s.vocab, s.d, 1), (s.d, s.vocab, 1)]


def decode_step_least_s(s: Sizes, bits: Sequence[int], rung: int, M: int,
                        peaks: Dict) -> float:
    """Least time of the packed matmuls of one decode step of M rows
    outside the experts: four attention matmuls a layer and the LM head,
    whose output is float32."""
    t = s.layers * sum(shapes.matmul_least_s(M, K, N, bits, rung, peaks)
                       for _, K, N in s.attention_matmuls())
    return t + shapes.matmul_least_s(M, s.d, s.vocab, bits, rung, peaks,
                                     out_bytes=4)


def expert_step_least_s(s: Sizes, bits: Sequence[int], rung: int, M: int,
                        peaks: Dict) -> float:
    """Least time of the grouped expert matmuls of one decode step of M
    rows: per layer and matmul, every held expert's packed streams at
    the rung and its float32 scales, read once, and the expected routed
    rows R = M * top_k * held / experts, their bf16 input and output and
    2 R K N FLOPs; the larger of bytes over bandwidth and FLOPs over
    the bf16 peak."""
    R = M * s.top_k * s.held / s.experts
    t = 0.0
    for _, K, N in s.expert_matmuls():
        nbytes = (s.held * (shapes.weight_bytes(K, N, bits, rung) + 4 * N)
                  + 2 * R * K + 2 * R * N)
        t += max(2.0 * R * K * N / peaks["bf16_flops"],
                 nbytes / peaks["hbm_bytes_per_s"])
    return s.layers * t


def token_flops(s: Sizes, context: int) -> int:
    """Model FLOPs of one token on this chip at ``context`` positions:
    2 per attention and router weight and per LM-head weight, 2 per
    weight of the experts it is expected to send here (top_k * held /
    experts of them), and QK^T and PV over the positions each layer
    sees (the window on sliding layers)."""
    d, E = s.d, s.experts
    matmul = s.layers * (sum(K * N for _, K, N in s.attention_matmuls())
                         + d * E)
    matmul += s.d * s.vocab
    expert = s.layers * s.top_k * s.held * 3 * d * s.ff // E
    attn = sum(4 * s.heads * s.head_dim *
               (min(context, s.window) if kind == "sliding" else context)
               for kind in s.layer_types)
    return 2 * matmul + 2 * expert + attn


def decode_flops(s: Sizes, rows: Sequence[Tuple[int, int]],
                 steps: int) -> int:
    """Useful model FLOPs of ``steps`` decode steps over real rows of
    (prompt tokens, answer tokens), counted as the dense decoder's."""
    total = 0
    for plen, m in rows:
        for j in range(min(steps, m - 1)):
            total += token_flops(s, plen + j + 1)
    return total

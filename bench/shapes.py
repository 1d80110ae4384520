"""Sizes of a configuration file, and the operations and bytes its work needs.

This is the benchmark's yardstick: every byte and FLOP count that a
roofline or utilization metric divides by is computed from the
configuration's shapes, never read from the program under test.
``Sizes``, ``decode_step_least_s``, ``token_flops`` and ``decode_flops``
are the dense decoder's, called through ``arch/dense.py``; the packing
arithmetic below them serves every architecture.  It mirrors the stored
layout of a NestQuant ladder: a
``bits[0]``-bit base stream plus one ``(gap + 1)``-bit delta stream per
rung, each split into power-of-two bit planes packed into 32-bit words
along the reduction axis K, in blocks of ``block`` rows.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

WORD_BITS = 32
PREFERRED_BLOCK = 512


@dataclass(frozen=True)
class Sizes:
    """The shape-bearing numbers of one configuration file."""
    d: int
    ff: int
    heads: int
    kv_heads: int
    head_dim: int
    layers: int
    vocab: int
    rope_theta: float
    eps: float
    qkv_bias: bool
    tied: bool

    @classmethod
    def from_config(cls, c: Dict) -> "Sizes":
        return cls(d=c["hidden_size"], ff=c["intermediate_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   layers=c["num_hidden_layers"], vocab=c["vocab_size"],
                   rope_theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]),
                   qkv_bias=bool(c["attention_bias"]),
                   tied=bool(c["tie_word_embeddings"]))

    def layer_matmuls(self) -> List[Tuple[str, int, int]]:
        """(name, K, N) of the seven matmuls of one decoder layer."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return [("q", self.d, q), ("k", self.d, kv), ("v", self.d, kv),
                ("o", q, self.d), ("gate", self.d, self.ff),
                ("up", self.d, self.ff), ("down", self.ff, self.d)]

    def quantized_leaves(self) -> List[Tuple[int, int, int]]:
        """(K, N, count) of every weight the ladder quantizes: the layer
        matmuls, the embedding table (V, d) and the LM head (d, V)."""
        out = [(K, N, self.layers) for _, K, N in self.layer_matmuls()]
        return out + [(self.vocab, self.d, 1), (self.d, self.vocab, 1)]


def choose_block(K: int, preferred: int = PREFERRED_BLOCK) -> int:
    """Largest power-of-two block <= ``preferred`` that divides K, else K."""
    b = preferred
    while b >= 32:
        if K % b == 0:
            return b
        b //= 2
    return K


def bit_planes(width: int) -> Tuple[int, ...]:
    """Power-of-two split of a ``width``-bit field (3 -> (2, 1))."""
    return tuple(1 << i for i in reversed(range(width.bit_length()))
                 if (width >> i) & 1)


def stream_bytes(K: int, N: int, width: int) -> int:
    """Bytes of one packed ``width``-bit stream of a (K, N) weight."""
    block = choose_block(K)
    rows = sum(math.ceil(block / (WORD_BITS // p)) for p in bit_planes(width))
    return math.ceil(K / block) * rows * N * 4


def stream_widths(bits: Sequence[int]) -> Tuple[int, ...]:
    """Stored widths of a ladder's streams: the base, then gap + 1 bits
    for each delta (the compensation bit is kept per level)."""
    bits = sorted(bits)
    return (bits[0],) + tuple(b - a + 1 for a, b in zip(bits, bits[1:]))


def weight_bytes(K: int, N: int, bits: Sequence[int], rung: int) -> int:
    """Packed bytes a matmul reads at ``rung``: base plus ``rung`` deltas."""
    return sum(stream_bytes(K, N, w) for w in stream_widths(bits)[:rung + 1])


def delta_bytes(leaves: Sequence[Tuple[int, int, int]], bits: Sequence[int],
                k: int) -> int:
    """bytes(delta_k): what a switch between rungs k and k + 1 moves, for
    quantized leaves given as (K, N, count)."""
    w = stream_widths(bits)[1 + k]
    return sum(stream_bytes(K, N, w) * n for K, N, n in leaves)


def matmul_least_s(M: int, K: int, N: int, bits: Sequence[int], rung: int,
                   peaks: Dict, out_bytes: int = 2) -> float:
    """The least time one packed (M, K) x (K, N) matmul can take: the
    larger of its FLOPs over the bf16 peak and its bytes (packed streams,
    one float32 scale per column, bf16 input, the output) over HBM
    bandwidth."""
    nbytes = (weight_bytes(K, N, bits, rung) + 4 * N + 2 * M * K
              + out_bytes * M * N)
    return max(2.0 * M * K * N / peaks["bf16_flops"],
               nbytes / peaks["hbm_bytes_per_s"])


def decode_step_least_s(s: Sizes, bits: Sequence[int], rung: int, M: int,
                        peaks: Dict) -> float:
    """Least time of every packed matmul of one decode step of M rows:
    seven per layer and the LM head, whose output is float32."""
    t = s.layers * sum(matmul_least_s(M, K, N, bits, rung, peaks)
                       for _, K, N in s.layer_matmuls())
    return t + matmul_least_s(M, s.d, s.vocab, bits, rung, peaks, out_bytes=4)


def token_flops(s: Sizes, context: int) -> int:
    """Model FLOPs of one token that attends over ``context`` positions:
    2 per matmul weight (the LM head included, the embedding gather
    excluded) plus QK^T and PV over the context in every layer."""
    matmul = s.layers * sum(K * N for _, K, N in s.layer_matmuls())
    matmul += s.d * s.vocab
    attn = s.layers * 4 * s.heads * s.head_dim * context
    return 2 * matmul + attn


def decode_flops(s: Sizes, rows: Sequence[Tuple[int, int]],
                 steps: int) -> int:
    """Useful model FLOPs of ``steps`` decode steps over a batch whose
    real rows are (prompt tokens, answer tokens) pairs.  Decode step j
    turns token j into token j + 1, so a row of m answer tokens uses the
    steps j < m - 1, each attending over its prompt and tokens 0..j."""
    total = 0
    for plen, m in rows:
        for j in range(min(steps, m - 1)):
            total += token_flops(s, plen + j + 1)
    return total

"""Operations and bytes an algorithm needs, from its shapes alone. Kept
with the benchmark so that no PR that claims a gain can change the count.
"""

from __future__ import annotations

from math import prod

DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "f64": 8, "s64": 8}


def nbytes(shapes) -> int:
    return sum(DTYPE_BYTES[d] * prod(dims) for d, dims in shapes)


def gpt2_params(cfg: dict) -> int:
    """N of the published GPT-2: token and position embeddings (the head
    is tied, so it adds nothing), and per layer the four d x d attention
    matrices, the two d x 4d MLP matrices, their biases and two layer
    norms; one final layer norm."""
    d, L = cfg["n_embd"], cfg["n_layer"]
    inner = cfg.get("n_inner") or 4 * d
    per_layer = (4 * d * d + 4 * d) + (2 * d * inner + inner + d) + 4 * d
    return cfg["vocab_size"] * d + cfg["n_positions"] * d + L * per_layer \
        + 2 * d


def gpt2_train_flops_per_token(cfg: dict, seq: int) -> float:
    """6N + 12 L d seq (Chowdhery et al. 2022, PaLM, appendix B): a
    multiply-add in the forward pass and two in the backward for every
    parameter — the embedding table is counted because the tied head
    multiplies by it — plus attention's QK^T and PV products over the
    whole sequence, not halved for causality, as the formula is defined.
    Recomputation is not counted: this is what the passes REQUIRE."""
    return 6.0 * gpt2_params(cfg) + 12.0 * cfg["n_layer"] * cfg["n_embd"] * seq


def flash_forward(b, h, sq, sk, d, causal: bool, ebytes: int = 2):
    """(flops, bytes) of one attention forward: QK^T and PV, 2 flops a
    multiply-add, halved under a causal mask (the masked half need not be
    computed); q, k, v read and o written once, the float32 log-sum-exp
    written once."""
    flops = 4.0 * b * h * sq * sk * d * (0.5 if causal else 1.0)
    nbytes_ = ebytes * b * h * d * (2 * sq + 2 * sk) + 4 * b * h * sq
    return flops, float(nbytes_)


def flash_backward(b, h, sq, sk, d, causal: bool, ebytes: int = 2):
    """(flops, bytes) of one attention backward: recompute S = QK^T, then
    dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q — five products; q,
    k, v, dO read and dq, dk, dv written once; lse and delta read."""
    flops = 10.0 * b * h * sq * sk * d * (0.5 if causal else 1.0)
    nbytes_ = ebytes * b * h * d * (3 * sq + 4 * sk) + 8 * b * h * sq
    return flops, float(nbytes_)


def classify_flash(kernel: dict):
    """A traced Pallas call -> ("fwd"|"bwd", b, h, sq, sk, d) if its
    shapes are those of the repo's flash attention, else None. Forward:
    (q, k, v) -> (o, lse[..., 1]); backward: six operands -> (dq, dk, dv).
    """
    outs, ops = kernel["outputs"], kernel["operands"]
    if len(ops) < 3 or any(len(dims) != 4 for _, dims in ops[:3]):
        return None
    (b, h, sq, d), (_, _, sk, _) = ops[0][1], ops[1][1]
    if len(outs) == 2 and tuple(outs[1][1]) == (b, h, sq, 1):
        return "fwd", b, h, sq, sk, d
    if len(outs) == 3 and len(ops) == 6:
        return "bwd", b, h, sq, sk, d
    return None

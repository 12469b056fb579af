"""Model FLOPs of one training step of the Granite 4.0-H hybrid, by the
convention of PaLM's appendix B (arXiv:2204.02311), and the SSD's own
operations and bytes for its roofline.

Forward FLOPs are 2 per multiply-add of every weight (the Mamba-2 in and
out projections, the depthwise conv, the attention projections, the SwiGLU
MLP and the tied LM head; the embedding gather left out), plus the
attention scores and values, 2 * 2 * S^2 * heads * head_dim per attention
layer and sequence with no discount for the causal mask, plus the SSD's
matmuls (``ssd_forward_flops_per_row``).  A step is three forward passes'
worth; recomputation by remat is not counted.
"""

from __future__ import annotations


def _vocab(program: dict) -> int:
    m = program.get("vocab_pad_to", 16)
    return -(-program["vocab_size"] // m) * m


def _mamba(program: dict):
    H, P, G, N = (program[k] for k in ("ssm_heads", "ssm_head_dim", "ssm_groups", "ssm_state_dim"))
    return H, P, G, N


def ssd_forward_flops_per_row(program: dict, seq: int) -> float:
    """One Mamba-2 layer's SSD, forward, one row, counted from the shapes as
    the chunked algorithm computes them: C.B^T within each chunk of L
    positions (L N G per position), its masked product with x (L P H), the
    chunk states B^T x and what they give, C . state (N P H each)."""
    H, P, G, N = _mamba(program)
    L = program["ssm_chunk"]
    return 2.0 * seq * (L * N * G + L * P * H + 2 * N * P * H)


def ssd_forward_bytes_per_row(program: dict, seq: int) -> float:
    """x, dt, B and C read and y written once, in bf16."""
    H, P, G, N = _mamba(program)
    return 2.0 * seq * (2 * H * P + H + 2 * G * N)


def _layers(program: dict, kind: str) -> int:
    pattern = program["pattern"]
    return sum(p["kind"] == kind for p in pattern) * program["num_layers"] // len(pattern)


def forward_flops_per_row(program: dict, seq: int) -> float:
    d, f = program["d_model"], program["d_ff"]
    H, P, G, N = _mamba(program)
    di, conv = H * P, H * P + 2 * G * N
    qd = program["num_heads"] * program["head_dim"]
    kvd = program["num_kv_heads"] * program["head_dim"]
    ffn_w = 3 * d * f
    mamba_w = d * (di + conv + H) + program["ssm_conv_width"] * conv + di * d
    attn_w = d * qd + 2 * d * kvd + qd * d
    n_mamba, n_attn = _layers(program, "mamba2"), _layers(program, "attn")
    flops = 2 * seq * (n_mamba * (mamba_w + ffn_w) + n_attn * (attn_w + ffn_w))
    flops += 4 * n_attn * seq * seq * qd  # attention scores and values
    flops += n_mamba * ssd_forward_flops_per_row(program, seq)
    flops += 2 * seq * d * _vocab(program)  # LM head
    return float(flops)


def model_flops(program: dict, traffic: dict) -> float:
    """Model FLOPs of one step over the global batch."""
    return 3.0 * traffic["global_batch"] * forward_flops_per_row(program, traffic["seq_len"])


def ssd_step_work(program: dict, traffic: dict):
    """(FLOPs, bytes) of every SSD of one step over the global batch: four
    forward passes' worth (forward, its recomputation, and the backward as
    two), a lower bound of the work whatever implements it."""
    rows, S = traffic["global_batch"], traffic["seq_len"]
    n = 4 * rows * _layers(program, "mamba2")
    return n * ssd_forward_flops_per_row(program, S), n * ssd_forward_bytes_per_row(program, S)

"""Model FLOPs of one training step of a decoder-only or encoder-decoder
transformer, by the convention of PaLM's appendix B (arXiv:2204.02311).

Forward FLOPs are 2 per multiply-add of every matmul weight, the LM head
included and the embedding gather left out, plus the attention scores and
values: 2 * 2 * S_q * S_kv * heads * head_dim per layer and sequence, with
no discount for the causal mask (as PaLM counts them).  A step is three
forward passes' worth (forward and backward); recomputation by remat is
not counted.  Whisper's cross-attention projects keys and values from the
encoder frames, so those two matmuls are counted once per frame.
"""

from __future__ import annotations


def _vocab(program: dict) -> int:
    m = program.get("vocab_pad_to", 16)
    return -(-program["vocab_size"] // m) * m


def forward_flops_per_row(program: dict, seq: int) -> float:
    d, f = program["d_model"], program["d_ff"]
    qd = program["num_heads"] * program["head_dim"]
    kvd = program["num_kv_heads"] * program["head_dim"]
    attn_w = d * qd + 2 * d * kvd + qd * d  # q, k, v, o
    ffn_w = d * f * (3 if program["act"] == "swiglu" else 2)
    L = program["num_layers"]
    flops = 2 * seq * L * (attn_w + ffn_w)  # decoder self-attention and FFN
    flops += 4 * L * seq * seq * qd  # decoder self-attention scores and values
    flops += 2 * seq * d * _vocab(program)  # LM head
    E = program.get("encoder_layers", 0)
    if E:
        frames = program["encoder_seq"]
        flops += 2 * frames * E * (attn_w + ffn_w)  # encoder layers
        flops += 4 * E * frames * frames * qd  # encoder self-attention
        flops += 2 * seq * L * (d * qd + qd * d)  # cross-attention q and o
        flops += 2 * frames * L * (2 * d * kvd)  # cross-attention k and v
        flops += 4 * L * seq * frames * qd  # cross-attention scores and values
    return float(flops)


def model_flops(program: dict, traffic: dict) -> float:
    """Model FLOPs of one step over the global batch."""
    return 3.0 * traffic["global_batch"] * forward_flops_per_row(program, traffic["seq_len"])

"""The model-FLOPs function of ``step_mfu`` against hand counts."""

from bench_chip_helpers import harness

flops = harness.load_module("flops", "transformer")


def test_encoder_decoder_hand_count():
    # d 8, d_ff 16, 2 heads of 4, 1 encoder + 1 decoder layer, 3 frames,
    # 2 decoder tokens, vocab 16, one row.
    program = dict(d_model=8, d_ff=16, num_heads=2, num_kv_heads=2, head_dim=4, act="gelu",
                   num_layers=1, vocab_size=16, vocab_pad_to=16, encoder_layers=1,
                   encoder_seq=3)
    fwd = (
        2 * 2 * (8 * 8 * 4)  # decoder q, k, v, o on 2 tokens
        + 2 * 2 * (2 * 8 * 16)  # decoder FFN
        + 2 * 2 * (8 * 8 * 2)  # cross-attention q and o on 2 tokens
        + 2 * 3 * (8 * 8 * 2)  # cross-attention k and v on 3 frames
        + 2 * 3 * (8 * 8 * 4 + 2 * 8 * 16)  # encoder layer on 3 frames
        + 2 * 2 * 8 * 16  # LM head
        + 2 * 2 * (2 * 2 * 8)  # decoder self-attention: scores and values
        + 2 * 2 * (2 * 3 * 8)  # cross-attention: scores and values
        + 2 * 2 * (3 * 3 * 8)  # encoder self-attention: scores and values
    )
    assert fwd == 7520
    assert flops.model_flops(program, {"global_batch": 1, "seq_len": 2}) == 3 * 7520


def test_decoder_only_gqa_hand_count():
    # d 8, d_ff 16, 4 query heads of 2 over 2 KV heads, 2 layers, 4 tokens,
    # vocab 10 padded to 16, two rows.
    program = dict(d_model=8, d_ff=16, num_heads=4, num_kv_heads=2, head_dim=2, act="gelu",
                   num_layers=2, vocab_size=10, vocab_pad_to=16)
    per_layer = 2 * 4 * (8 * 8 + 8 * 4 + 8 * 4 + 8 * 8 + 2 * 8 * 16) + 2 * 2 * 4 * 4 * 8
    fwd = 2 * per_layer + 2 * 4 * 8 * 16
    assert fwd == 9216
    assert flops.model_flops(program, {"global_batch": 2, "seq_len": 4}) == 2 * 3 * 9216


def test_cell_figures(capsys):
    figures = {}
    for workload in ("whisper-medium.train.1chip", "starcoder2-3b-l6.train.1chip"):
        cell = harness.load_cell(workload)
        figures[workload] = flops.model_flops(cell.config["program"], cell.traffic)
        print(f"{workload}: {figures[workload]:.4e} model FLOPs per step")
    assert 4.0e13 < figures["whisper-medium.train.1chip"] < 4.3e13
    assert 4.2e13 < figures["starcoder2-3b-l6.train.1chip"] < 4.5e13

"""The whole step's share of the chip's peak: model FLOPs per trained
token (benchmark/work.py: 6 per matmul parameter and the causal
attention term; recomputation not counted) times the tokens per second
of the window, over the bf16 peak."""

from benchmark import work


def read(r):
    f = r["facts"]
    rate = f["tokens"] / f["window_s"]
    flops = work.train_flops_per_token(r["lm"], f["seq"])
    return 100.0 * flops * rate / r["peaks"]["bf16_flops_per_s"]

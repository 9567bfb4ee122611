"""The whole block-diffusion step's share of the chip's bf16 peak: model
FLOPs of a step (benchmark/work_sdar.py: layer products over 2L
positions, the held experts over the expected copies, attention over
the keys the mask shows, the head over the expected masked positions;
forward plus twice that, recomputation not counted) times the steps per
second of the window (its ROW tokens over batch * seq), over the peak."""

from benchmark import work_sdar


def read(r):
    f = r["facts"]
    if not f.get("window_s") or not f.get("tokens"):
        return None
    steps_per_s = f["tokens"] / f["window_s"] / (f["batch"] * f["seq"])
    flops = work_sdar.train_flops_per_step(r["lm"], f["batch"], f["seq"])
    return 100.0 * flops["total"] * steps_per_s / r["peaks"]["bf16_flops_per_s"]

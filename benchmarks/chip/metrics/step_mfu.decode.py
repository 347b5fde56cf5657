"""Decode step: the whole step's roofline share, the larger of required
operations over peak FLOP/s and required bytes over peak HBM bytes/s
(``work.py``), over the measured time a step, in percent. Decode is bound
by bytes: the weights are read once a step."""


def read(r):
    p = r["peaks"]
    least = max(r["step_flops"] / p["bf16_flops_per_s"],
                r["step_bytes"] / p["hbm_bytes_per_s"])
    return least / (r["window_s"] / r["steps"]) * 100

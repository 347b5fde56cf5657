"""Train step: required operations a token (``work.py``, PaLM convention)
times tokens a second, over the chips' bf16 peak, in percent."""


def read(r):
    return (r["flops_per_token"] * r["tokens_per_s"]
            / (r["chips"] * r["peaks"]["bf16_flops_per_s"]) * 100)

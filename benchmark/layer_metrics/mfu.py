"""Model FLOP/s utilization inside the traced sub-window: forward+backward
FLOPs per token (recompute not counted) x tokens of the whole steps in it,
over its length on the host clock (both edges drained) and the chips' bf16
peak."""
from benchmark.lib import roofline


def read(ctx):
    cfg, facts = ctx["cell"]["config"], ctx["facts"]
    trace, job = facts["trace"], facts["job"]
    if not trace.get("steps"):
        return None
    rate = trace["steps"] * facts["tokens_per_step"] / trace["window_s"] \
        / facts["world"]
    flops = roofline.gpt2_train_flops_per_token(
        cfg["n_layer"], cfg["n_embd"], cfg["vocab_size"], job["block"])
    return 100.0 * flops * rate / ctx["peaks"]["bf16_flops_per_s"]

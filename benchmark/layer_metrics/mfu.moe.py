"""Model FLOP/s utilization of a step whose FFN is a dropless top-k expert
layer, inside the traced sub-window: forward + backward FLOPs a token of THIS
cut (projections, router, the picks HELD by the program's own counters, the
band's and the causal half's scores, the head over the rows held; recompute
not counted: `lib/expert_train.train_flops_per_token`) x tokens of the whole
steps in it, over its length on the host clock (both edges drained) and the
chip's bf16 peak. Where no counter was drained the held share is the
configuration's (experts held over the router's outputs)."""
from benchmark.lib import expert_train


def read(ctx):
    cfg, facts = ctx["cell"]["config"], ctx["facts"]
    trace, job = facts["trace"], facts["job"]
    if not trace.get("steps"):
        return None
    counters = expert_train.step_counters(ctx)
    share = (counters["moe_assignments"] / counters["moe_routed"]
             if counters and counters["moe_routed"] else
             cfg["num_experts"] / expert_train.routed_experts(cfg))
    rate = trace["steps"] * facts["tokens_per_step"] / trace["window_s"] \
        / facts["world"]
    flops = expert_train.train_flops_per_token(cfg, job["block"], share)
    return 100.0 * flops * rate / ctx["peaks"]["bf16_flops_per_s"]

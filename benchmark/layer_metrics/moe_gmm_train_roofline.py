"""The trained grouped products' share of their roofline: the larger of their
FLOPs over the bf16 peak (the picks held x nine products of hidden x expert
width: three forward, six gradient) and their least HBM bytes over the HBM
peak (`lib/expert_train`), over the kernels' device time a step. The rows are
the program's own `moe_assignments` a step (summed over layers and
microbatches, drained at `logging_steps`); a remat rung's second forward is
in the time and not in the work. Never clamped."""
from benchmark.lib import expert_train
from benchmark.lib.layer_common import kernel_ms_per_unit


def read(ctx):
    ms = kernel_ms_per_unit(ctx, expert_train.GMM_TRAIN_KERNELS)
    counters = expert_train.step_counters(ctx)
    if ms is None or not counters or not counters["moe_assignments"]:
        return None
    cfg, job, peaks = ctx["cell"]["config"], ctx["facts"]["job"], ctx["peaks"]
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = counters["moe_assignments"] / ctx["facts"]["world"]
    calls = cfg["num_hidden_layers"] * job["accum"]
    by_flops = expert_train.gmm_train_flops(rows, d, f) \
        / peaks["bf16_flops_per_s"]
    by_bytes = expert_train.gmm_train_bytes(
        rows, calls, cfg["num_experts"], d, f) / peaks["hbm_bytes_per_s"]
    return 100.0 * max(by_flops, by_bytes) * 1e3 / ms

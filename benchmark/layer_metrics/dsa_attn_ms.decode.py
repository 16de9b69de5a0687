"""Device time a decode tick of latent attention over the kept set
(`dsa_attn`, ops/pallas_mla_attn under a mask: one call a full layer), by
kernel name in the trace: the kernel's events that started inside an
execution of the engine's decode program, over the executions in the trace.
A program without the kernel, or a trace that names no program, reports
nothing."""
from benchmark.lib import dsa_layers, hyper_stream
from benchmark.lib.layer_common import device0


def read(ctx):
    plane = device0(ctx)
    if plane is None:
        return None
    found = hyper_stream.kernel_s_in(plane, dsa_layers.ATTN_KERNEL,
                                     dsa_layers.DECODE_MODULE)
    if not found or found[0] <= 0:
        return None
    return found[0] * 1e3 / found[1]

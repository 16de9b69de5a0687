"""Device time a decode tick of the hyper-connection mix's two kernels
(`mhc_pre`, `mhc_post`, ops/pallas_mhc: one call each a sublayer), by kernel
name in the trace: the kernels' events that started inside an execution of
the engine's decode program, over the executions in the trace (the same
kernels inside a prefill are not in it). A program without the kernels, or a
trace that names no program, reports nothing."""
from benchmark.lib import hyper_stream
from benchmark.lib.layer_common import device0


def read(ctx):
    plane = device0(ctx)
    if plane is None:
        return None
    found = hyper_stream.kernel_s_in(plane, hyper_stream.MHC_KERNELS,
                                     hyper_stream.DECODE_MODULE)
    if not found or found[0] <= 0:
        return None
    return found[0] * 1e3 / found[1]

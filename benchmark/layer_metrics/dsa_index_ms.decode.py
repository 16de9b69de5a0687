"""Device time a decode tick of the learned indexer's scoring kernel
(`dsa_index`, ops/pallas_dsa: one call a full layer over the row's index-key
pages in place), by kernel name in the trace: the kernel's events that
started inside an execution of the engine's decode program, over the
executions in the trace (`lib/hyper_stream.kernel_s_in`: a prefill scores
its own fresh keys in XLA and is not in it). The selection that follows the
scores (`ops/dsa.kept_positions`: counting passes in XLA under the scope
`dsa/select`) carries no name a trace's op line shows and is NOT in this
number: `scripts/scope_times.py` gives it by scope (PERF.md section 5). A
program without the kernel, or a trace that names no program, reports
nothing."""
from benchmark.lib import dsa_layers, hyper_stream
from benchmark.lib.layer_common import device0


def read(ctx):
    plane = device0(ctx)
    if plane is None:
        return None
    found = hyper_stream.kernel_s_in(plane, dsa_layers.INDEX_KERNEL,
                                     dsa_layers.DECODE_MODULE)
    if not found or found[0] <= 0:
        return None
    return found[0] * 1e3 / found[1]

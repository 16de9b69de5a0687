"""Device time a prefill of a latent family's attention over its own fresh
rows (`latent_prefill`, ops/pallas_dsa: one call a latent layer of a prefill
dispatched from position 0), by kernel name in the trace: the kernel's events
that started inside an execution of the engine's prefill program, over those
executions (`prefill_device_ms.decode`'s programs). A program without the
kernel (a commit before it, a bucket or a family the rule does not take: the
chunked XLA walk), or a trace that names no prefill, reports nothing."""
from benchmark.lib import hyper_stream
from benchmark.lib.layer_common import device0

LATENT_PREFILL_KERNEL = r"latent_prefill"
# the engine's prefill program among the trace's executed programs
# (prefill_device_ms.decode's pattern)
PREFILL_MODULE = r"^jit_prefill\b"


def read(ctx):
    plane = device0(ctx)
    if plane is None:
        return None
    found = hyper_stream.kernel_s_in(plane, LATENT_PREFILL_KERNEL,
                                     PREFILL_MODULE)
    if not found or found[0] <= 0:
        return None
    return found[0] * 1e3 / found[1]

"""The (page, kv head) pairs the decode lists hand attention as a share of
what a walk of every page would hand it, over the traced window's decode
dispatches: the engine's `kv_pages_selected` (one for every pair a list
holds, summed over both `minicpm4` layers; counted IN the decode program from
the lists it builds and brought back behind the tokens) over `kv_pages_read`
(one layer's `ceil(L / 16)` of every live row: the host's arithmetic, as in
every serving cell) x kv heads x the configuration's count of `minicpm4`
layers. The counter that says selection engaged: a program that walks every
page reads 100%; 64 blocks of 64 out of 10-20k positions read some 30%.
Source: program_counter."""
from benchmark.lib import sparse_linear
from benchmark.lib.latent_moe import counter_delta


def read(ctx):
    selected = counter_delta(ctx, "kv_pages_selected")
    walked = counter_delta(ctx, "kv_pages_read")
    cfg = ctx["cell"]["config"]
    if selected is None or not walked or "mixer_types" not in cfg:
        return None
    return 100.0 * selected / sparse_linear.pages_walked(walked, cfg)

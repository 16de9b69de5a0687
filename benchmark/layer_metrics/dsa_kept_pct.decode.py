"""The keys the decode rows attend as a share of the keys they can see, over
the traced window's decode dispatches: the engine's `dsa_keys_kept` over
`dsa_keys_visible`, both counted IN the decode program from the masks the
attention is handed (summed over rows, ticks and full layers) and brought
back behind the tokens. The counter that says selection engaged: a program
that attends every visible position reads 100%; 2,048 of 8-16k positions
read 12-25%. Source: program_counter."""
from benchmark.lib.latent_moe import counter_delta


def read(ctx):
    kept = counter_delta(ctx, "dsa_keys_kept")
    visible = counter_delta(ctx, "dsa_keys_visible")
    if kept is None or not visible:
        return None
    return 100.0 * kept / visible

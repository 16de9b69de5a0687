"""Pages one window layer's walk reads as a share of the pages one full
layer's walk reads, over the traced window's decode dispatches: the engine's
`kv_window_pages_read` over `kv_pages_read`. The first is counted in the
decode program from the walk the kernel is handed (the pages of each row's
own length in it: what the kernel reads), so a program with a wrong window
moves it and one that hands the kernel whole rows reads 100%; the second is
the host's arithmetic on the rows' lengths, as in every serving cell. A ring
that bounds the window reads at most 33 pages a row where the full layer
reads the row's whole length. Source: program_counter."""
from benchmark.lib.latent_moe import counter_delta


def read(ctx):
    window = counter_delta(ctx, "kv_window_pages_read")
    full = counter_delta(ctx, "kv_pages_read")
    if window is None or not full:
        return None
    return 100.0 * window / full

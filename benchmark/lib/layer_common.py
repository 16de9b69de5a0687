"""Arithmetic the per-layer readers share. ``ctx`` is what ``run.py`` hands
every reader: ``cell``, ``trace`` (plain data, see ``lib/xplane``),
``facts`` (the driver's counters and spans) and ``peaks``."""

from __future__ import annotations

import statistics

from benchmark.lib import xplane


def traced_ticks(ctx) -> list:
    t = ctx["facts"]["trace"]
    return [x for x in ctx["facts"].get("ticks", [])
            if t["t0"] <= x["t0"] and x["t1"] <= t["t1"]]


def units(ctx) -> int:
    """Optimizer steps (training) or engine ticks (serving) inside the
    traced window."""
    t = ctx["facts"]["trace"]
    return int(t["steps"]) if "steps" in t else len(traced_ticks(ctx))


def device0(ctx):
    planes = xplane.device_planes(ctx["trace"])
    return planes[0] if planes else None


def busy_ms_per_unit(ctx):
    n = units(ctx)
    if not n or device0(ctx) is None:
        return None
    return xplane.device_busy_s(ctx["trace"]) * 1e3 / n


def idle_ms_per_unit(ctx):
    n = units(ctx)
    if not n or device0(ctx) is None:
        return None
    window = ctx["facts"]["trace"]["window_s"]
    return (window - xplane.device_busy_s(ctx["trace"])) * 1e3 / n


def kernel_ms_per_unit(ctx, pattern: str):
    n, plane = units(ctx), device0(ctx)
    if not n or plane is None:
        return None
    secs = xplane.matching_s(plane, pattern)
    return secs * 1e3 / n if secs > 0 else None


def median_tick_ms(ctx, keep):
    durs = [(t["t1"] - t["t0"]) * 1e3 for t in ctx["facts"].get("ticks", [])
            if keep(t)]
    return statistics.median(durs) if durs else None


def median_of(ctx, key):
    values = ctx["facts"].get(key) or []
    return statistics.median(values) if values else None


# ---- kernels by name. On the chip the flash kernels are flash_attention
# (forward), jvp_jit_flash_attention (the forward inside remat) and
# flash_mha_bwd_dq / _dkv; the Lion kernels are the Mosaic calls the program
# names lion_ballot and lion_apply (ops/pallas_lion), 2 a leaf a step. By
# name alone: an unnamed Mosaic call, or another kernel's, is not Lion time.
FLASH_KERNELS = r"flash_attention|flash_mha"
LION_KERNELS = r"lion_ballot|lion_apply"
PAGED_ATTN_KERNEL = r"paged_attn"


def peak_hbm_gb(ctx):
    """Peak bytes on the fullest chip (``harness.memory_peak_bytes``, read
    when the window closed), in GB."""
    return ctx["facts"]["memory_peak_bytes"] / 1e9


def decode_only_tick_ms(ctx):
    """Median wall time of ``engine.step()`` on decode-only ticks (no
    prefill in the tick), from the benchmark's own span around the call."""
    return median_tick_ms(ctx, lambda t: t["prefills"] == 0
                          and t["decode_tokens"] > 0)


def slots_busy_pct(ctx):
    """Active slots over max_seqs, mean over the window's ticks (a count)."""
    ticks = ctx["facts"].get("ticks") or []
    if not ticks:
        return None
    return 100.0 * sum(t["active"] for t in ticks) / len(ticks) \
        / ctx["facts"]["max_seqs"]

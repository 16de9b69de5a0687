"""Device time by named region, from a profiler trace of this program.

    python scripts/scope_times.py <trace_dir> [--per N]

``trace_dir`` is what ``--profile_dir`` (or ``--trace_on_anomaly``, or any
``jax.profiler.start_trace``) wrote. The model and op files put
``jax.named_scope`` regions around their parts and the Lion kernels carry
names (``SCOPES`` below). On the chip every device op's ``op_name`` (XProf's
"Framework op name": ``jit(train_step)/.../jvp(xent)/.../reduce_max``)
holds the regions it was traced under, and XProf shows them three ways: the
Trace Viewer's "Framework Name Scope" line on each ``/device:TPU:<n>``,
the HLO Stats table's "Framework op name" column, and the op profile. This
script is the second as one table: each op's self time goes to the
innermost region in its name (``jvp(...)`` and ``transpose(...)`` are the
forward-under-autodiff and backward of the same region), ``(no scope)``
is what XLA made without a framework name (layout copies, some fusions).
``--per N`` divides by the steps or ticks the trace holds.

Needs the ``xprof`` package (the TensorBoard profile plugin's converter)
to read the ``.xplane.pb``; :func:`by_scope` itself is plain Python over
the HLO Stats table.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import sys

SCOPES = ("embed", "attn", "mlp", "head", "xent", "paged_attn",
          "paged_scatter", "paged_gather", "vote/pack", "vote/unpack",
          "vote/tally", "vote/wire", "lion_ballot", "lion_apply",
          "lion_stats", "mla/q", "mla/kv_latent", "mla_attn",
          "mla_paged_attn", "moe/route", "moe/sort", "moe/experts",
          "moe/shared", "moe/combine", "moe_gmm", "attn/qkv", "attn/rope",
          "attn/gate", "window_attn", "full_attn", "kda/conv", "kda/gate",
          "kda/step", "kda/chunk", "kda/out_norm", "kda_step", "kda_chunk",
          "lightning/step", "lightning/chunk", "lightning/out_norm",
          "lightning_step", "lightning_chunk", "sparse/compress",
          "sparse/select", "sparse_attn", "dense_attn", "mhc/pre",
          "mhc/post", "mhc/read_out", "mhc_pre", "mhc_post",
          "flash_gqa_fwd", "attn/window", "attn/full", "moe",
          "moe_gmm_drhs", "flash_gqa_lse", "flash_gqa_di", "flash_gqa_dq",
          "flash_gqa_dkv", "qk_rope_fwd", "qk_rope_bwd", "dsa/index",
          "dsa/select", "dsa/attn", "window_mla", "dsa_index", "dsa_attn",
          "window_mla_attn", "dsa_prefill", "latent_prefill")
NO_SCOPE = "(no scope)"
_FIND = [(s, re.compile(r"(?<=[(/])%s(?=[)/])" % re.escape(s)))
         for s in SCOPES]


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` in a device op's framework name: the one
    that ends last, and of two that end together the longer (``dsa/attn``,
    not the ``attn`` it ends in)."""
    path = "/" + (op_name or "")
    found = [(m.end(), len(s), s) for s, pat in _FIND
             for m in pat.finditer(path)]
    return max(found)[2] if found else NO_SCOPE


def by_scope(hlo_stats: dict) -> dict:
    """``{program: {scope: self microseconds}}`` from XProf's HLO Stats
    table (``{"cols": [{"id": ...}], "rows": [{"c": [{"v": ...}]}]}``). A
    program is called by the head its ops' names share (``jit(train_step)``)
    and its id."""
    cols = [c["id"] for c in hlo_stats["cols"]]
    prog, name, self_us = (cols.index(k) for k in (
        "program_id", "tf_op_name", "total_self_time"))
    out: dict = collections.defaultdict(collections.Counter)
    heads: dict = collections.defaultdict(collections.Counter)
    for row in hlo_stats.get("rows", ()):
        cells = [c.get("v") if isinstance(c, dict) else c for c in row["c"]]
        op_name, us = cells[name] or "", float(cells[self_us])
        out[str(cells[prog])][scope_of(op_name)] += us
        if "/" in op_name:
            heads[str(cells[prog])][op_name.split("/", 1)[0]] += us
    return {(heads[p].most_common(1)[0][0] + " " if heads[p] else "") + p:
            dict(c) for p, c in out.items()}


def hlo_stats_of(trace_dir: str) -> dict:
    """XProf's HLO Stats of the newest ``.xplane.pb`` under ``trace_dir``."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise SystemExit(f"scope_times: no .xplane.pb under {trace_dir}")
    try:
        from xprof.convert import raw_to_tool_data
    except ImportError:
        raise SystemExit("scope_times: needs the xprof package") from None
    data, _ = raw_to_tool_data.xspace_to_tool_data([paths[-1]], "hlo_stats", {})
    return json.loads(data)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--per", type=float, default=1.0,
                    help="steps or ticks in the trace (divides the times)")
    args = ap.parse_args(argv)
    for program, scopes in sorted(by_scope(hlo_stats_of(args.trace_dir)).items(),
                                  key=lambda kv: -sum(kv[1].values())):
        total = sum(scopes.values())
        print(f"program {program}: {total / 1e3 / args.per:.2f} ms")
        for scope, us in sorted(scopes.items(), key=lambda kv: -kv[1]):
            print(f"  {scope:14s} {us / 1e3 / args.per:10.3f} ms "
                  f"{100 * us / total:6.2f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Large-world vote: W > 127 promotes the ballot accumulator to int32.

collectives.vote_total uses int8 ballots only while |sum| <= 127
(sign_psum) / group tallies fit (hier); at W=130 the tally must promote —
run in a subprocess because conftest pins this process to 8 devices.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap


def test_world_130_int32_promotion():
    code = textwrap.dedent("""
        import jax
        jax.config.update("jax_platforms", "cpu")
        import numpy as np, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from _sharded import run_sharded
        from distributed_lion_tpu.parallel.collectives import (
            majority_vote, vote_total)

        W = 130
        assert len(jax.devices()) >= W
        mesh = Mesh(np.array(jax.devices()[:W]), ("data",))
        votes = np.random.default_rng(0).random((W, 64)) < 0.5

        def body(v):
            t = vote_total(v[0], "data", "sign_psum")
            return t[None], majority_vote(v[0], "data", "hier:13")[None]

        totals, hier = run_sharded(body, mesh, P("data"),
                                   (P("data"), P("data")), jnp.asarray(votes))
        count = votes.sum(0)
        np.testing.assert_array_equal(np.asarray(totals[0]), count * 2 - W)
        assert np.asarray(totals).dtype == np.int32
        # hier at W=130 g=13: majority-of-majorities is replica-consistent
        h = np.asarray(hier)
        for w in range(1, W):
            np.testing.assert_array_equal(h[0], h[w])
        print("OK")
    """)
    env = dict(os.environ)
    env.update({"XLA_FLAGS": "--xla_force_host_platform_device_count=130",
                "PYTHONPATH": os.pathsep.join([".", "tests"])})
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=300,
        env=env,
        cwd=".",
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout

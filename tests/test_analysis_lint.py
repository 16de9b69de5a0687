"""graft-check tier 1 (analysis/lint.py): every rule has a fixture file
proving it fires, the suppression syntax works, traced-scope detection has
the documented boundary, and — the CI pin — the package itself lints
clean (zero findings), so any future violation of a codified pitfall
fails tier-1 instead of waiting for a chip run."""

import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "distributed_lion_tpu")
FIXTURES = os.path.join(REPO, "tests", "fixtures", "analysis")

# load lint.py by FILE PATH, the way dependency-light scripts must be able
# to (scripts/check_evidence.py runs on boxes without jax; importing the
# package's modules would pull in jax)
_spec = importlib.util.spec_from_file_location(
    "graft_lint", os.path.join(PKG, "analysis", "lint.py"))
lint = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(lint)


# ------------------------------------------------------------------ fixtures
RULE_FIXTURES = {
    "DLT001": ("dlt001_host_sync.py", 4),
    "DLT002": ("dlt002_nondeterminism.py", 3),
    "DLT003": ("dlt003_host_callback.py", 2),
    "DLT004": ("dlt004_prng_save.py", 1),
    "DLT005": ("dlt005_axis_literal.py", 3),
    "DLT006": ("dlt006_swallowed.py", 2),
    "DLT007": ("dlt007_json.py", 2),
    "DLT008": ("dlt008_mutable_default.py", 2),
    # the DLT009 fixture sits under fixtures/analysis/train/ so the
    # path-scoped rule (bare print under a train//data/ directory) applies
    # to it the same way it applies to distributed_lion_tpu/train/
    "DLT009": (os.path.join("train", "dlt009_bare_print.py"), 2),
    # DLT010/DLT011 are serve/-scoped the same way (host-loop hygiene for
    # the serving plane, ISSUE 19)
    "DLT010": (os.path.join("serve", "dlt010_host_loop_device_alloc.py"),
               3),
    "DLT011": (os.path.join("serve", "dlt011_wall_clock.py"), 3),
    # DLT012 (ISSUE 20): blocking socket/pipe reads need a deadline seam
    # in serve/ — the process-isolated fleet's heartbeat verdicts depend
    # on reads that return
    "DLT012": (os.path.join("serve", "dlt012_blocking_socket.py"), 3),
}


@pytest.mark.parametrize("rule", sorted(RULE_FIXTURES))
def test_rule_fires_on_its_fixture(rule):
    """Each rule fires exactly the marked number of times on its fixture —
    and nothing else fires there (single-rule fixtures keep failures
    attributable)."""
    fixture, expected = RULE_FIXTURES[rule]
    findings = lint.lint_file(os.path.join(FIXTURES, fixture))
    assert [f.rule for f in findings] == [rule] * expected, (
        f"{fixture}: {[str(f) for f in findings]}")


def test_every_documented_rule_has_a_fixture():
    assert set(RULE_FIXTURES) == set(lint.RULES)


def test_clean_fixture_has_zero_findings():
    assert lint.lint_file(os.path.join(FIXTURES, "clean.py")) == []


# -------------------------------------------------------------- suppressions
def test_line_suppression():
    src = (
        "import json\n"
        "def f(r):\n"
        "    return json.dumps(r)  # graft: disable=DLT007\n"
    )
    assert lint.lint_source(src) == []


def test_file_suppression():
    src = (
        "# graft: disable-file=DLT008\n"
        "def f(x, acc=[]):\n"
        "    return acc\n"
        "def g(x, acc=[]):\n"
        "    return acc\n"
    )
    assert lint.lint_source(src) == []


def test_suppression_in_string_or_docstring_is_inert():
    """Suppressions live in COMMENT tokens only: a module that merely
    DOCUMENTS the syntax in a docstring (as analysis/lint.py itself does)
    must not silently disable rules on itself."""
    src = (
        '"""Docs: suppress with `# graft: disable-file=DLT006`."""\n'
        "def f(p):\n"
        "    try:\n"
        "        p.unlink()\n"
        "    except Exception:\n"
        "        pass\n"
    )
    assert [f.rule for f in lint.lint_source(src)] == ["DLT006"]
    quoted = 'x = "# graft: disable=DLT008"\ndef f(a=[]):\n    return a\n'
    assert [f.rule for f in lint.lint_source(quoted)] == ["DLT008"]


def test_suppression_is_rule_specific():
    src = (
        "import json\n"
        "def f(r, acc=[]):  # graft: disable=DLT007\n"
        "    return json.dumps(r)\n"
    )
    # the DLT008 on line 2 is NOT covered by the DLT007 suppression; the
    # DLT007 itself is on line 3, not the suppressed line
    rules = [f.rule for f in lint.lint_source(src)]
    assert "DLT008" in rules and "DLT007" in rules


# ------------------------------------------------------- traced-scope bounds
def test_partial_shard_map_decorator_is_traced():
    src = (
        "from functools import partial\n"
        "import jax\n"
        "@partial(jax.shard_map, mesh=None, in_specs=None, out_specs=None)\n"
        "def step(x):\n"
        "    return float(x)\n"
    )
    assert [f.rule for f in lint.lint_source(src)] == ["DLT001"]


def test_nested_function_inherits_traced_scope():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def step(xs):\n"
        "    def micro(x):\n"
        "        return x.item()\n"
        "    return micro(xs)\n"
    )
    assert [f.rule for f in lint.lint_source(src)] == ["DLT001"]


def test_host_code_is_not_traced_scope():
    src = (
        "def log(metrics):\n"
        "    print('loss', float(metrics['loss']))\n"
    )
    assert lint.lint_source(src) == []


def test_lint_paths_under_hidden_ancestor(tmp_path):
    """The hidden-component skip applies BELOW the lint root only: a repo
    checked out under a hidden ancestor (~/.cache, a .worktrees dir) must
    still lint — an empty file list reading 'clean' is a false-green CI
    gate."""
    root = tmp_path / ".hidden" / "pkg"
    root.mkdir(parents=True)
    (root / "bad.py").write_text("def f(x, acc=[]):\n    return acc\n")
    assert [f.rule for f in lint.lint_paths([root])] == ["DLT008"]
    # hidden children below the root are still skipped
    sub = root / ".venv"
    sub.mkdir()
    (sub / "x.py").write_text("def g(a=[]):\n    return a\n")
    assert [f.rule for f in lint.lint_paths([root])] == ["DLT008"]


# --------------------------------------------------------------- the CI pins
def test_package_lints_clean():
    """THE tier-1 pin: zero graft-check findings over the whole package.
    A new violation of any codified pitfall fails here, with the rule and
    line in the assertion message."""
    findings = lint.lint_paths([PKG])
    assert findings == [], "\n".join(str(f) for f in findings)


def test_cli_exit_codes(tmp_path):
    """python -m distributed_lion_tpu.analysis: exit 0 on a clean tree,
    1 with findings — the contract scripts/ci_static.sh and the runbook's
    static stage rely on."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    ok = subprocess.run(
        [sys.executable, "-m", "distributed_lion_tpu.analysis", PKG],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    bad = tmp_path / "bad.py"
    bad.write_text("def f(x, acc=[]):\n    return acc\n")
    r = subprocess.run(
        [sys.executable, "-m", "distributed_lion_tpu.analysis", str(bad)],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert r.returncode == 1 and "DLT008" in r.stdout


def test_lint_runs_standalone_without_package():
    """lint.py is pure stdlib AND directly runnable by path — the no-jax
    contract (scripts/ci_static.sh uses exactly this invocation)."""
    r = subprocess.run(
        [sys.executable, os.path.join(PKG, "analysis", "lint.py"), PKG],
        cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "clean" in r.stdout


def test_guard_fixture_and_guard_modules_clean():
    """ISSUE 5 satellite: the vote guard's step-side code must stay free
    of host syncs — the quarantine decision runs on the host one dispatch
    behind, never inside the compiled step. The fixture shows the
    forbidden shape (DLT001 fires on a step that host-reads the health
    mask / guard observations); the guard's real modules lint clean by
    file path."""
    findings = lint.lint_file(
        os.path.join(FIXTURES, "guard_step_host_sync.py"))
    assert [f.rule for f in findings] == ["DLT001", "DLT001"], (
        [str(f) for f in findings])
    for rel in ("train/vote_guard.py", "optim/distributed_lion.py",
                "parallel/collectives.py"):
        path = os.path.join(PKG, rel)
        assert lint.lint_file(path) == [], rel


def test_control_plane_fixture_and_modules_clean():
    """ISSUE 10 satellite: membership is a host-side decision — the
    control plane consumes drop/rejoin signals at dispatch boundaries and
    the step only consumes the pushed mask. The path-scoped fixture under
    fixtures/analysis/control_plane/ shows the forbidden shape (DLT001
    fires twice on a step that host-reads the membership schedule / alive
    mask); the real control-plane modules lint zero-finding by file
    path."""
    findings = lint.lint_file(os.path.join(
        FIXTURES, "control_plane", "dlt001_membership_host_read.py"))
    assert [f.rule for f in findings] == ["DLT001", "DLT001"], (
        [str(f) for f in findings])
    for rel in ("train/control_plane.py", "train/vote_guard.py",
                "train/resilience.py", "train/loop.py"):
        path = os.path.join(PKG, rel)
        assert lint.lint_file(path) == [], rel


def test_serve_fixture_and_serve_modules_clean():
    """ISSUE 9 satellite: the serving engine's decode tick must never
    host-read per token — the classic serving pitfall (an `int(token)` /
    EOS branch inside the jitted tick serializes the rolling batch). The
    path-scoped fixture under fixtures/analysis/serve/ shows the
    forbidden shape (DLT001 fires twice); the real serving modules lint
    clean by file path — the engine's ONE host read per tick happens at
    the dispatch boundary, outside traced scope."""
    findings = lint.lint_file(os.path.join(
        FIXTURES, "serve", "dlt001_decode_tick_host_read.py"))
    assert [f.rule for f in findings] == ["DLT001", "DLT001"], (
        [str(f) for f in findings])
    for rel in ("serve/engine.py", "serve/kv_cache.py", "serve/api.py",
                "ops/attention.py", "cli/run_serve.py"):
        path = os.path.join(PKG, rel)
        assert lint.lint_file(path) == [], rel


def test_tp_serve_fixtures_and_serve_parallel_modules_clean():
    """ISSUE 13 satellite: TP serving code must (a) never hardcode a
    mesh-axis string literal — the engine threads parallel.mesh's
    TENSOR_AXIS through its shard_map specs and the models' psum exits
    (DLT005 fires 3× on the fixture showing the forbidden shape), and
    (b) never host-read per token inside the SHARD_MAP'd decode tick —
    worse than the single-device pitfall, it serializes the whole slice
    (DLT001 fires 2× on its fixture). Every module under serve/ and
    parallel/ lints zero-finding by file path."""
    findings = lint.lint_file(os.path.join(
        FIXTURES, "serve", "dlt005_tp_axis_literal.py"))
    assert [f.rule for f in findings] == ["DLT005"] * 3, (
        [str(f) for f in findings])
    findings = lint.lint_file(os.path.join(
        FIXTURES, "serve", "dlt001_sharded_tick_host_read.py"))
    assert [f.rule for f in findings] == ["DLT001", "DLT001"], (
        [str(f) for f in findings])
    for sub in ("serve", "parallel"):
        base = os.path.join(PKG, sub)
        for name in sorted(os.listdir(base)):
            if not name.endswith(".py"):
                continue
            path = os.path.join(base, name)
            assert lint.lint_file(path) == [], f"{sub}/{name}"


def test_expert_axis_fixture_and_moe_serve_modules_clean():
    """ISSUE 15 satellite: MoE serving code must never hardcode the
    expert mesh-axis string literal — the engine threads parallel.mesh's
    EXPERT_AXIS through its (data=1, expert=ep, tensor=tp) shard_map mesh
    and the model hooks' ``ep_axis``, and parallel/expert.moe_ffn binds
    whatever axis name the caller passes (DLT005 fires 3× on the fixture
    showing the forbidden shape). parallel/expert.py and every serve-path
    module the MoE route touches lint zero-finding by file path."""
    findings = lint.lint_file(os.path.join(
        FIXTURES, "serve", "dlt005_expert_axis_literal.py"))
    assert [f.rule for f in findings] == ["DLT005"] * 3, (
        [str(f) for f in findings])
    for rel in ("parallel/expert.py", "models/gpt2.py",
                "models/generate.py", "serve/engine.py",
                "serve/speculate.py", "serve/kv_cache.py",
                "cli/run_serve.py"):
        path = os.path.join(PKG, rel)
        assert lint.lint_file(path) == [], rel


def test_ep_batch_axis_fixture_and_touched_modules_clean():
    """ISSUE 16 satellite: the batch-sharded decode path (slots
    ``P(EXPERT_AXIS)``, page pools sharded on their block dim) and the
    training balance-ring psum must never hardcode the mesh-axis string —
    the fixture shows the forbidden shapes (DLT005 fires 4×: slot spec,
    pool spec with both axes literal-named, psum default). Every module
    ISSUE 16 touched lints zero-finding by file path."""
    findings = lint.lint_file(os.path.join(
        FIXTURES, "serve", "dlt005_ep_batch_axis_literal.py"))
    assert [f.rule for f in findings] == ["DLT005"] * 4, (
        [str(f) for f in findings])
    for rel in ("parallel/expert.py", "parallel/mesh.py",
                "models/gpt2.py", "serve/engine.py", "serve/speculate.py",
                "train/loop.py", "cli/run_serve.py", "optim/lion.py",
                "optim/distributed_lion.py"):
        path = os.path.join(PKG, rel)
        assert lint.lint_file(path) == [], rel


def test_migration_fixture_and_replica_plane_clean():
    """ISSUE 14 satellite: a migration re-prefill must never host-read
    per committed token — replaying a migrated request's history with an
    `int(tok)`/logits branch inside the jitted dispatch pays
    len(committed) round trips per migration and serializes the
    survivor's batch. The fixture shows the forbidden shape (DLT001 fires
    twice); serve/replica_plane.py (pure host-side scheduling) and the
    engine's resumption path lint zero-finding by file path — the real
    re-prefill is ONE bucketed dispatch with one boundary host read."""
    findings = lint.lint_file(os.path.join(
        FIXTURES, "serve", "dlt001_migration_host_read.py"))
    assert [f.rule for f in findings] == ["DLT001", "DLT001"], (
        [str(f) for f in findings])
    for rel in ("serve/replica_plane.py", "serve/engine.py"):
        path = os.path.join(PKG, rel)
        assert lint.lint_file(path) == [], rel


def test_speculate_fixture_and_module_clean():
    """ISSUE 11 satellite: the speculative verify dispatch must never
    host-read per DRAFT token — an `int(accept[i])` acceptance branch
    inside the jitted verify loop pays one device→host round trip per
    proposed token and erases the dispatch amortization speculation
    exists to buy. The fixture shows the forbidden shape (DLT001 fires
    twice); serve/speculate.py lints zero-finding by file path — its one
    host read per tick (tokens + accept counts) happens at the dispatch
    boundary, and accept/rollback are pure host block-table math."""
    findings = lint.lint_file(os.path.join(
        FIXTURES, "serve", "dlt001_verify_host_read.py"))
    assert [f.rule for f in findings] == ["DLT001", "DLT001"], (
        [str(f) for f in findings])
    assert lint.lint_file(os.path.join(PKG, "serve", "speculate.py")) == []


def test_blocking_io_fixture_and_net_modules_clean():
    """ISSUE 20 satellite: the serving plane's socket/pipe transports
    must never block unboundedly — a dead peer behind an unbounded
    recv/accept wedges every request in the host loop, and the
    process-isolated fleet's heartbeat verdicts depend on reads that
    return. The fixture shows the forbidden shapes (DLT012 fires 3×:
    accept, recv, os.read — and shows the two legal seams plus the
    suppression); every code path in the new socket front and the pipe
    transport lints zero-finding by file path."""
    findings = lint.lint_file(
        os.path.join(FIXTURES, "serve", "dlt012_blocking_socket.py"))
    assert [f.rule for f in findings] == ["DLT012"] * 3, (
        [str(f) for f in findings])
    for rel in ("serve/net.py", "serve/fleet_proc.py",
                "serve/replica_worker.py", "serve/fleet_state.py",
                "serve/replica_plane.py"):
        assert lint.lint_file(os.path.join(PKG, rel)) == [], rel


def test_metrics_fixture_and_metrics_module_clean():
    """ISSUE 17 satellite: the metrics plane must never host-read a
    device value — a lifecycle hook stamping TTFT from `int(tok[0])`
    inside the jitted tick would add the per-token sync the plane exists
    to observe, and "metrics on" would no longer be observationally
    free. The fixture shows the forbidden shape (DLT001 fires three
    times); serve/metrics.py lints zero-finding by file path — every
    stamp rides host work the tick loop already does — and the engine's
    instrumented tick loop stays clean too."""
    findings = lint.lint_file(os.path.join(
        FIXTURES, "serve", "dlt001_metrics_host_read.py"))
    assert [f.rule for f in findings] == ["DLT001", "DLT001", "DLT001"], (
        [str(f) for f in findings])
    for rel in ("serve/metrics.py", "serve/engine.py",
                "serve/replica_plane.py"):
        assert lint.lint_file(os.path.join(PKG, rel)) == [], rel


# ------------------------------------------------------ the tests' own idiom
# Files let off the pass below, each with its reason.
EAGER_SHARD_MAP_ALLOWED = {
    "test_chip_compile.py":
        "never runs a body: it lowers and compiles each shard_map for a "
        "described v5e, explicitly, through its own _compile",
}


def eager_shard_map_calls(path):
    """``(line, what)`` for every ``shard_map(...)`` in the file whose
    result is called outside ``jit``: called on the spot
    (``shard_map(f, ...)(x)``), or bound to a name that the same function
    then calls. A function decorated with ``jax.jit`` (or a ``partial`` of
    it) is compiled whole, and ``jax.jit(shard_map(...))`` hands the result
    to ``jit`` and not to a call: neither is a finding."""
    import ast

    def is_shard_map(node):
        return isinstance(node, ast.Call) and (
            getattr(node.func, "id", None) == "shard_map"
            or getattr(node.func, "attr", None) == "shard_map")

    def is_jit(dec):
        if isinstance(dec, ast.Call):       # jax.jit(...), partial(jax.jit, ...)
            return is_jit(dec.func) or any(is_jit(a) for a in dec.args)
        return getattr(dec, "id", getattr(dec, "attr", None)) == "jit"

    found = []

    def walk(node, bound):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(is_jit(d) for d in node.decorator_list):
                return
            bound = bound | {
                t.id for a in ast.walk(node) if isinstance(a, ast.Assign)
                and is_shard_map(a.value)
                for t in a.targets if isinstance(t, ast.Name)}
        if isinstance(node, ast.Call):
            if is_shard_map(node.func):
                found.append((node.lineno, "shard_map(...)(...)"))
            elif getattr(node.func, "id", None) in bound:
                found.append((node.lineno, f"{node.func.id}(...), a "
                              "shard_map bound to a name"))
        for child in ast.iter_child_nodes(node):
            walk(child, bound)

    with open(path, encoding="utf-8") as f:
        walk(ast.parse(f.read(), path), frozenset())
    return found


def test_tests_run_their_shard_map_bodies_compiled():
    """ISSUE 35: a ``shard_map`` called outside ``jit`` dispatches its body
    primitive by primitive over the eight device threads (17 s for a 0.7 s
    election), and enough of them cut tier-1 at its limit. Tests run a body
    through ``tests/_sharded.py`` (``run_sharded`` / ``sharded``) or under
    their own ``jax.jit``."""
    tests = os.path.join(REPO, "tests")
    found = []
    for name in sorted(os.listdir(tests)):
        if name.endswith(".py") and name not in EAGER_SHARD_MAP_ALLOWED:
            found += [f"tests/{name}:{line}: {what}" for line, what in
                      eager_shard_map_calls(os.path.join(tests, name))]
    assert not found, "eager shard_map, use tests/_sharded.py:\n" + \
        "\n".join(found)
    assert set(EAGER_SHARD_MAP_ALLOWED) <= set(os.listdir(tests))

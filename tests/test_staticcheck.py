"""staticcheck gate tests: the shipped tree must audit clean, each
seeded regression fixture must stay flagged, and the recompile sentinel
must count exactly one compile per campaign signature (and catch a
deliberate shape drift)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import p2p_gossip_tpu as pg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Registry / jaxpr auditor
# ---------------------------------------------------------------------------

def test_registry_covers_every_engine_layer():
    from p2p_gossip_tpu.staticcheck import entrypoints, registry

    entrypoints.load_all()
    names = {e.name for e in registry.all_entries()}
    # One representative per layer: a new engine dropping out of the
    # registry should fail loudly here, not silently shrink the audit.
    for required in (
        "engine.sync._run_chunk_while",
        "engine.sync._run_chunk_coverage",
        "batch.campaign._run_coverage_batch",
        "batch.campaign._run_while_batch",
        "models.protocols._run_pushpull_replicas",
        "models.protocols._run_pushk_replicas",
        "parallel.engine_sharded.flood_runner",
        "parallel.engine_sharded.flood_runner[delta]",
        "parallel.protocols_sharded.pushpull_runner",
        "parallel.protocols_sharded.pushpull_runner[delta]",
        "parallel.exchange.compress_deltas[delta]",
        "parallel.exchange.scatter_deltas[delta]",
        "ops.ell.propagate",
        "ops.segment.scatter_or",
        "ops.bitmask.coverage_per_slot",
    ):
        assert required in names, f"{required} missing from audit registry"
    counted = {e.name for e in registry.countable_entries()}
    assert "batch.campaign._run_coverage_batch" in counted
    assert "models.protocols._run_pushpull_replicas" in counted


def test_jaxpr_audit_shipped_tree_green():
    from p2p_gossip_tpu.staticcheck.jaxpr_audit import run_audit

    report = run_audit()
    assert report["entries_audited"] >= 19
    assert report["ok"], json.dumps(report["violations"], indent=2)


def test_jaxpr_audit_flags_forbidden_primitive():
    """A debug print inside a kernel must be rejected (rule J3)."""
    import jax
    import jax.numpy as jnp

    from p2p_gossip_tpu.staticcheck.jaxpr_audit import audit_entry
    from p2p_gossip_tpu.staticcheck.registry import AuditEntry, AuditSpec

    def chatty(x):
        jax.debug.print("x={x}", x=x)
        return x + 1

    entry = AuditEntry(
        name="test.chatty", fn=chatty,
        spec=lambda: AuditSpec(args=(jnp.zeros(3, dtype=jnp.int32),)),
    )
    rules = {v.rule for v in audit_entry(entry)}
    assert "no-host-callback" in rules


def test_jaxpr_audit_flags_word_width_drift():
    """A uint32 signature array packed to the wrong minor width must be
    rejected (rule J6 — the bitmask packing contract)."""
    import jax.numpy as jnp

    from p2p_gossip_tpu.staticcheck.jaxpr_audit import audit_entry
    from p2p_gossip_tpu.staticcheck.registry import AuditEntry, AuditSpec

    def widened(seen):
        return jnp.concatenate([seen, seen], axis=-1)  # W -> 2W drift

    entry = AuditEntry(
        name="test.widened", fn=widened,
        spec=lambda: AuditSpec(
            args=(jnp.zeros((4, 2), dtype=jnp.uint32),), bitmask_words=2
        ),
    )
    rules = {v.rule for v in audit_entry(entry)}
    assert "bitmask-words" in rules


# ---------------------------------------------------------------------------
# AST lint
# ---------------------------------------------------------------------------

def test_ast_lint_shipped_tree_green():
    from p2p_gossip_tpu.staticcheck.astlint import run_lint

    report = run_lint()
    assert report["files_scanned"] > 40
    assert report["ok"], json.dumps(report["violations"], indent=2)


def test_lint_flags_numpy_and_tracer_branch_in_jit():
    from p2p_gossip_tpu.staticcheck.astlint import lint_source

    src = (
        "import functools\n"
        "import jax\n"
        "import numpy as np\n"
        "@functools.partial(jax.jit, static_argnames=('k',))\n"
        "def bad(x, t, *, k):\n"
        "    if k:\n"            # static arg: allowed
        "        pass\n"
        "    if t > 0:\n"        # traced param: flagged
        "        pass\n"
        "    y = np.sqrt(x)\n"   # numpy on a tracer: flagged
        "    return y\n"
    )
    rules = [v.rule for v in lint_source(src, "snippet.py")]
    assert rules.count("tracer-branch") == 1
    assert rules.count("numpy-in-jit") == 1


def test_lint_allows_structure_tests_and_split_keys():
    from p2p_gossip_tpu.staticcheck.astlint import lint_source

    src = (
        "import functools\n"
        "import jax\n"
        "@functools.partial(jax.jit)\n"
        "def fine(x, churn=None):\n"
        "    if churn is None:\n"       # structure test: allowed
        "        pass\n"
        "    if x.ndim == 2:\n"         # shape attribute: allowed
        "        pass\n"
        "    return x\n"
        "def keys(seed):\n"
        "    key = jax.random.PRNGKey(seed)\n"
        "    k1, k2 = jax.random.split(key)\n"
        "    a = jax.random.uniform(k1, (3,))\n"
        "    key = jax.random.fold_in(key, 1)\n"  # rebind re-arms budget
        "    b = jax.random.normal(key, (3,))\n"
        "    return a, b\n"
    )
    assert lint_source(src, "snippet.py") == []


def test_lint_flags_seed_offset_literal():
    from p2p_gossip_tpu.models.seeds import LOSS_SEED_OFFSET
    from p2p_gossip_tpu.staticcheck.astlint import lint_source

    src = f"SEED = 3 + {LOSS_SEED_OFFSET}\n"
    rules = [v.rule for v in lint_source(src, "p2p_gossip_tpu/foo.py")]
    assert rules == ["seed-offset-literal"]


def test_seed_helpers_match_historic_offsets():
    """The consolidation must not move the streams: solo runs and
    campaign replicas derived under the old literals must reproduce."""
    from p2p_gossip_tpu.models.seeds import (
        churn_stream_seed,
        loss_stream_seed,
        replica_loss_seeds,
    )

    assert loss_stream_seed(5) == 5 + 104729
    assert churn_stream_seed(5) == 5 + 7919
    assert replica_loss_seeds([7, 8]) == [7 + 104729, 8 + 104729]


# ---------------------------------------------------------------------------
# Recompile sentinel
# ---------------------------------------------------------------------------

def test_sentinel_one_compile_per_replica_campaign():
    """The headline invariant: an R-replica campaign (multiple batches)
    is exactly ONE compile of its kernel, and a warm rerun adds none."""
    import jax

    from p2p_gossip_tpu.batch.campaign import (
        _run_coverage_batch,
        flood_replicas,
        run_coverage_campaign,
    )

    graph = pg.erdos_renyi(48, 0.15, seed=0)
    replicas = flood_replicas(graph, 2, list(range(4)), 16)
    jax.clear_caches()
    run_coverage_campaign(graph, replicas, 16, batch_size=2)  # 2 batches
    assert _run_coverage_batch._cache_size() == 1
    run_coverage_campaign(graph, replicas, 16, batch_size=2)  # warm
    assert _run_coverage_batch._cache_size() == 1


def test_sentinel_grid_replay_matches_expected():
    from p2p_gossip_tpu.staticcheck.recompile import run_sentinel

    spec = {
        "numNodes": 48, "p": 0.15, "shares": 2, "horizon": 12,
        "replicas": 4, "protocol": ["push", "pushpull"],
    }
    report = run_sentinel(spec)
    assert report.cells == 2
    assert report.expected == {
        "coverage_batch": 1, "while_batch": 0,
        "pushpull_replicas": 1, "pushk_replicas": 0,
    }
    assert report.ok, report.violations()


def test_sentinel_catches_shape_drift():
    from p2p_gossip_tpu.staticcheck.fixtures import recompile_fixture

    report = recompile_fixture()
    assert not report["ok"]
    assert report["measured"]["coverage_batch"] == 2
    assert any(
        "compiled 2x" in v["message"] for v in report["violations"]
    )


def test_expected_compiles_model_counts_static_axes():
    """Pure-host check of the signature model: loss thresholds are
    static (one compile each), protocols route to their own kernels,
    fanout collapses for non-pushk cells."""
    from p2p_gossip_tpu.staticcheck.recompile import expected_compiles

    spec = {
        "numNodes": 64, "p": 0.1, "shares": 2, "horizon": 16,
        "replicas": 4,
        "protocol": ["push", "pushpull", "pull", "pushk"],
        "lossProb": [0.0, 0.1], "fanout": [2, 3],
    }
    expected = expected_compiles(spec)
    assert expected["coverage_batch"] == 2       # 2 loss thresholds
    assert expected["pushpull_replicas"] == 4    # 2 modes x 2 thresholds
    assert expected["pushk_replicas"] == 4       # 2 fanouts x 2 thresholds
    assert expected["while_batch"] == 0


# ---------------------------------------------------------------------------
# Fixtures stay flagged
# ---------------------------------------------------------------------------

def test_f64_fixture_flagged():
    from p2p_gossip_tpu.staticcheck.fixtures import f64_fixture

    report = f64_fixture()
    assert not report["ok"]
    assert {"forbid-64bit"} <= {v["rule"] for v in report["violations"]}


def test_prng_fixture_flagged():
    from p2p_gossip_tpu.staticcheck.fixtures import prng_fixture

    report = prng_fixture()
    assert not report["ok"]


def test_exchange_fixture_flagged():
    from p2p_gossip_tpu.staticcheck.fixtures import exchange_fixture

    report = exchange_fixture()
    assert not report["ok"]
    assert {"integer-only"} <= {v["rule"] for v in report["violations"]}


def test_async_fixture_flagged():
    from p2p_gossip_tpu.staticcheck.fixtures import async_fixture

    report = async_fixture()
    assert not report["ok"]
    assert {"integer-only"} <= {v["rule"] for v in report["violations"]}


# ---------------------------------------------------------------------------
# CLI contract (the thing ci_tier1.sh shells out to)
# ---------------------------------------------------------------------------

def _run_cli(*args, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "staticcheck.py"),
         *args],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=timeout,
    )


def test_cli_full_run_green_json():
    r = _run_cli("--json")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    report = json.loads(r.stdout.strip().splitlines()[-1])
    assert report["ok"] is True
    assert report["violations_total"] == 0
    assert report["jaxpr"]["entries_audited"] >= 19
    assert report["lint"]["files_scanned"] > 40
    assert report["recompile"]["ok"] is True


@pytest.mark.parametrize("fixture", ["f64", "recompile", "prng"])
def test_cli_fixture_exits_nonzero(fixture):
    r = _run_cli("--fixture", fixture, "--json")
    assert r.returncode == 1, (
        f"fixture {fixture} must exit non-zero (analyzer flagged it); "
        f"got rc={r.returncode}\n{r.stdout[-1000:]}{r.stderr[-1000:]}"
    )
    report = json.loads(r.stdout.strip().splitlines()[-1])
    assert report["fixture"] == fixture
    assert report["violations"]


def test_cli_lint_only_is_fast_and_green():
    r = _run_cli("--lint-only", "--json", timeout=120)
    assert r.returncode == 0, r.stdout[-1000:] + r.stderr[-1000:]
    report = json.loads(r.stdout.strip().splitlines()[-1])
    assert report["ok"] is True
    assert "jaxpr" not in report

"""Seeded regression fixtures — deliberately-bad inputs each analyzer
MUST keep flagging.

The analyzers gate tier-1; a refactor that silently blinds one of them
would leave the gate green while the guardrail is gone. Each fixture
here reproduces one historical failure mode on tiny shapes; the CLI's
``--fixture`` mode runs one and exits non-zero iff the analyzer still
flags it (tests/test_staticcheck.py asserts all three, and that the
shipped tree stays clean). This file is excluded from the AST lint scan
(astlint.EXCLUDE_PARTS) — it is bad on purpose.

Fixtures:

  f64        a "kernel" whose integer math weak-promotes through a
             Python float and lands on float64 under x64 — the dtype
             drift that changes every uint32 counter-hash coin
  recompile  one campaign cell run twice with a drifted replica batch
             size — the shape wobble that burns a sweep's compile
             budget silently
  prng       jax.random key consumed by two samplers without split() —
             correlated streams masquerading as independent replicas
  telemetry  metric rings forced on with the telemetry flag down — the
             always-on instrumentation that would silently break the
             zero-cost contract (telemetry_off.py must flag the ring
             avals in the supposedly-off trace)
  digest     state-digest ring forced on with the telemetry flag down —
             same zero-cost contract, separate detection channel: the
             digest ring is rank-1, so telemetry_off.py's T4 rule greps
             the OFF trace for the digest mix constants instead of
             scanning aval shapes
  exchange   a delta-exchange compaction whose rank/keep computation
             drifts through float32 — past ~2^24 cut rows the mantissa
             rounds the cumsum and a capacity-C buffer silently keeps
             the wrong words; the integer-only audit (J2) must flag the
             inexact avals
  async      a bounded-staleness accounting step (the async exchange's
             ``staleness`` telemetry column) whose late-fold tally
             drifts through float32 — past ~2^24 accumulated stale
             word-folds the column silently saturates low and the
             staleness <= (K-1) * stale_folds bound reads as satisfied
             when it is not; the integer-only audit (J2, same
             discipline as the real ``flood_runner[async]`` entries)
             must flag the inexact avals
"""

from __future__ import annotations

FIXTURES = (
    "f64", "recompile", "prng", "telemetry", "digest", "exchange",
    "meshfact", "async", "hub",
)


def f64_fixture() -> dict:
    """Trace a bad integer kernel under x64 and audit it: the auditor
    must report forbid-64bit (and integer-only) violations."""
    import jax
    import jax.numpy as jnp

    from p2p_gossip_tpu.staticcheck.jaxpr_audit import audit_entry
    from p2p_gossip_tpu.staticcheck.registry import AuditEntry, AuditSpec

    def bad_tick_update(seen):
        # The classic weak-type leak: a Python float in bitmask counter
        # math. Under x64 the promotion lands on float64.
        scaled = seen.astype(jnp.int64) * 2.0
        return scaled.sum()

    def spec():
        return AuditSpec(
            args=(jnp.zeros((4, 2), dtype=jnp.uint32),),
            integer_only=True,
        )

    entry = AuditEntry(
        name="fixtures.f64_bad_tick_update", fn=bad_tick_update, spec=spec
    )
    with jax.enable_x64(True):
        violations = audit_entry(entry)
    return {
        "fixture": "f64",
        "ok": not violations,  # must come back False
        "violations": [v.as_dict() for v in violations],
    }


def recompile_fixture() -> dict:
    """Run one campaign cell twice with a drifted batch size: the
    sentinel's cache counter must see two executables where the cell's
    signature model allows one."""
    import jax

    from p2p_gossip_tpu.batch.campaign import (
        _run_coverage_batch,
        flood_replicas,
        run_coverage_campaign,
    )
    from p2p_gossip_tpu.models.topology import erdos_renyi
    from p2p_gossip_tpu.staticcheck.recompile import SentinelReport

    graph = erdos_renyi(48, 0.15, seed=0)
    replicas = flood_replicas(graph, 2, [0, 1, 2, 3], 16)
    jax.clear_caches()
    run_coverage_campaign(graph, replicas, 16)
    # The deliberate shape drift: same cell, replica batch halved — the
    # (B, ...) leading axis changes and XLA compiles a second program.
    run_coverage_campaign(graph, replicas, 16, batch_size=2)
    expected = {"coverage_batch": 1}
    measured = {"coverage_batch": int(_run_coverage_batch._cache_size())}
    report = SentinelReport(
        ok=measured == expected, expected=expected, measured=measured,
        cells=1,
    )
    return {
        "fixture": "recompile",
        "ok": report.ok,  # must come back False
        "violations": [{"rule": "recompile-sentinel", "message": m}
                       for m in report.violations()],
        "expected": expected,
        "measured": measured,
    }


_PRNG_BAD_SOURCE = '''\
import jax


def sample_two_replicas(seed):
    key = jax.random.PRNGKey(seed)
    a = jax.random.uniform(key, (8,))
    b = jax.random.normal(key, (8,))  # same key: b correlates with a
    return a, b
'''


def prng_fixture() -> dict:
    """Lint a snippet that reuses a PRNG key across two samplers: the
    AST lint must report prng-key-reuse."""
    from p2p_gossip_tpu.staticcheck.astlint import lint_source

    violations = lint_source(_PRNG_BAD_SOURCE, "fixtures/prng_bad.py")
    flagged = [v for v in violations if v.rule == "prng-key-reuse"]
    return {
        "fixture": "prng",
        "ok": not flagged,  # must come back False
        "violations": [v.as_dict() for v in flagged],
    }


def telemetry_fixture() -> dict:
    """Force the metric rings on while the telemetry flag is down (the
    `rings._FIXTURE_FORCE` backdoor) and run the zero-cost check on one
    instrumented kernel: the checker must flag ring avals in the
    telemetry-OFF trace."""
    import jax

    from p2p_gossip_tpu.staticcheck.telemetry_off import run_telemetry_check
    from p2p_gossip_tpu.telemetry import rings

    rings._FIXTURE_FORCE = True
    # Cache discipline matters on BOTH edges: a pre-existing pjit trace
    # of the kernel would satisfy make_jaxpr without re-running the
    # (now-forced) trace-time gate, hiding the seeded bug; and a trace
    # taken while forced would poison the cache for later legitimate
    # telemetry=False calls.
    jax.clear_caches()
    try:
        report = run_telemetry_check(only=("engine.sync._run_chunk_while",))
    finally:
        rings._FIXTURE_FORCE = False
        jax.clear_caches()
    return {
        "fixture": "telemetry",
        "ok": report["ok"],  # must come back False
        "violations": report["violations"],
    }


def digest_fixture() -> dict:
    """Force the state-digest ring on while the telemetry flag is down
    (the `digest._FIXTURE_FORCE` backdoor) and run the zero-cost check on
    one instrumented kernel: the T4 rule must find the digest mix
    constants in the telemetry-OFF trace."""
    import jax

    from p2p_gossip_tpu.staticcheck.telemetry_off import run_telemetry_check
    from p2p_gossip_tpu.telemetry import digest

    digest._FIXTURE_FORCE = True
    # Same cache discipline as telemetry_fixture, both edges.
    jax.clear_caches()
    try:
        report = run_telemetry_check(only=("engine.sync._run_chunk_while",))
    finally:
        digest._FIXTURE_FORCE = False
        jax.clear_caches()
    return {
        "fixture": "digest",
        "ok": report["ok"],  # must come back False
        "violations": report["violations"],
    }


def exchange_fixture() -> dict:
    """Audit a deliberately-bad frontier-delta compaction: the write-side
    rank computation (which of a shard's changed bitmask words fit the
    fixed-capacity buffer) drifts through float32, the dtype leak that
    would silently drop the wrong words once the cut-row count passes
    the 2^24 mantissa. The integer-only audit (J2, same discipline the
    real ``parallel.exchange.compress_deltas`` entry is registered
    under) must flag the inexact avals."""
    import jax.numpy as jnp

    from p2p_gossip_tpu.staticcheck.jaxpr_audit import audit_entry
    from p2p_gossip_tpu.staticcheck.registry import AuditEntry, AuditSpec

    def bad_compress_deltas(changed, need):
        # The seeded bug: per-row ranks via a float32 cumsum. Exact only
        # below 2^24 rows — beyond it, equal ranks collide and the
        # capacity cut keeps a wrong subset, bitwise-silently.
        changed_rows = (changed != 0).any(axis=1) & need[:, 0]
        ranks = jnp.cumsum(changed_rows.astype(jnp.float32))
        keep = changed_rows & (ranks <= 8.0)
        return jnp.where(keep[:, None], changed, jnp.uint32(0))

    def spec():
        return AuditSpec(
            args=(
                jnp.zeros((16, 2), dtype=jnp.uint32),
                jnp.zeros((16, 1), dtype=jnp.bool_),
            ),
            integer_only=True,
        )

    entry = AuditEntry(
        name="fixtures.exchange_bad_compress_deltas",
        fn=bad_compress_deltas, spec=spec,
    )
    violations = audit_entry(entry)
    return {
        "fixture": "exchange",
        "ok": not violations,  # must come back False
        "violations": [v.as_dict() for v in violations],
    }


def hub_fixture() -> dict:
    """Audit a deliberately-bad hub overlay: the flat row ids the
    all_gathered hub block scatters back onto the reconstruction canvas
    (shard offset + local hub row) computed through float32 — exact
    only below 2^24 rows, beyond which two distinct hub rows round to
    one flat id and the overlay drops a hub's words, bitwise-silently.
    The integer-only audit (J2, same discipline as the real
    ``parallel.exchange.overlay_hub[hub]`` entry) must flag the inexact
    avals."""
    import jax.numpy as jnp

    from p2p_gossip_tpu.staticcheck.jaxpr_audit import audit_entry
    from p2p_gossip_tpu.staticcheck.registry import AuditEntry, AuditSpec

    def bad_overlay_hub(recon, hub_local, hub_block):
        # The seeded bug: per-shard row offsets via float32 arithmetic.
        k, h = hub_local.shape
        n_loc = recon.shape[0] // k
        offs = jnp.arange(k, dtype=jnp.float32) * jnp.float32(n_loc)
        flat = (hub_local.astype(jnp.float32) + offs[:, None])
        return recon.at[flat.astype(jnp.int32).reshape(-1)].set(hub_block)

    def spec():
        return AuditSpec(
            args=(
                jnp.zeros((16, 2), dtype=jnp.uint32),
                jnp.zeros((4, 2), dtype=jnp.int32),
                jnp.zeros((8, 2), dtype=jnp.uint32),
            ),
            integer_only=True,
        )

    entry = AuditEntry(
        name="fixtures.hub_bad_overlay",
        fn=bad_overlay_hub, spec=spec,
    )
    violations = audit_entry(entry)
    return {
        "fixture": "hub",
        "ok": not violations,  # must come back False
        "violations": [v.as_dict() for v in violations],
    }


def async_fixture() -> dict:
    """Audit a deliberately-bad async staleness accounting step: the
    per-tick ``staleness`` column (added-lateness word-folds charged
    against the pre-advance landed view) tallied through float32 — the
    dtype leak that saturates the counter low past the 2^24 mantissa
    and silently blesses a broken staleness bound. The integer-only
    audit (J2, the discipline the real async runner entries are
    registered under) must flag the inexact avals."""
    import jax.numpy as jnp

    from p2p_gossip_tpu.staticcheck.jaxpr_audit import audit_entry
    from p2p_gossip_tpu.staticcheck.registry import AuditEntry, AuditSpec

    def bad_staleness_row(landed_view, amounts):
        # The seeded bug: remote late-folds counted in float32. Exact
        # only below 2^24 folds — a 100K-node mesh at full frontier
        # blows past it within a run, rounding the column down.
        remote = (landed_view != 0).any(axis=-1)
        folds = remote.astype(jnp.float32).sum(axis=-1)
        stale = (folds * amounts.astype(jnp.float32)).sum()
        return stale.astype(jnp.uint32)

    def spec():
        return AuditSpec(
            args=(
                jnp.zeros((2, 16, 2), dtype=jnp.uint32),
                jnp.zeros((2,), dtype=jnp.int32),
            ),
            integer_only=True,
        )

    entry = AuditEntry(
        name="fixtures.async_bad_staleness_row",
        fn=bad_staleness_row, spec=spec,
    )
    violations = audit_entry(entry)
    return {
        "fixture": "async",
        "ok": not violations,  # must come back False
        "violations": [v.as_dict() for v in violations],
    }


def meshfact_fixture() -> dict:
    """Seeded axis-split drift: the campaign drivers bake the
    (replicas, nodes) factorization into every jit signature, so
    ``auto_axis_split`` must be stable under the few-percent wobble its
    "rough" node-byte estimate is allowed (``estimate_node_bytes``
    docstring) — an estimate that straddles a shard boundary silently
    recompiles every campaign batch. The fixture lands the estimate ON
    the 2-shard boundary and wobbles it +/-2%: a drift-stable model
    expects ONE distinct split; the sentinel must measure two."""
    from p2p_gossip_tpu.parallel.mesh import auto_axis_split
    from p2p_gossip_tpu.staticcheck.recompile import SentinelReport

    n_devices, hbm = 8, 1_000_000
    # The seeded bug: node_bytes / 2 == hbm exactly, so +2% drift tips
    # the factorization from (4, 2) to (2, 4).
    base = 2 * hbm
    splits = {
        auto_axis_split(n_devices, int(base * drift), hbm_bytes=hbm)
        for drift in (0.98, 1.0, 1.02)
    }
    expected = {"distinct_splits": 1}
    measured = {"distinct_splits": len(splits)}
    report = SentinelReport(
        ok=measured == expected, expected=expected, measured=measured,
        cells=3,
    )
    return {
        "fixture": "meshfact",
        "ok": report.ok,  # must come back False
        "violations": [{"rule": "meshfact-sentinel", "message": m}
                       for m in report.violations()],
        "expected": expected,
        "measured": measured,
    }


def run_fixture(name: str) -> dict:
    if name == "f64":
        return f64_fixture()
    if name == "recompile":
        return recompile_fixture()
    if name == "prng":
        return prng_fixture()
    if name == "telemetry":
        return telemetry_fixture()
    if name == "digest":
        return digest_fixture()
    if name == "exchange":
        return exchange_fixture()
    if name == "meshfact":
        return meshfact_fixture()
    if name == "async":
        return async_fixture()
    if name == "hub":
        return hub_fixture()
    raise ValueError(f"unknown fixture {name!r}; valid: {FIXTURES}")

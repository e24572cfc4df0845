"""Precision-robust SPDC: float32 as a first-class verified compute dtype.

The f32 protocol leg (DESIGN.md §6): growth-safe cipher relayout,
power-of-two equilibration, compensated log-det accumulation, growth-aware
ε(N) — plus the regression tests for the three numeric-comparison bugfixes
(bucket_size_for fallback, Determinant.allclose, Determinant.value).

This module is the x64-disabled CI leg: every test here passes with
JAX_ENABLE_X64=0 (tests comparing f32 against a live f64 protocol run are
skipped there; the f64 *references* come from numpy, which the x64 switch
does not touch).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Determinant, ServerFault, cipher, equilibrate, keygen,
    outsource_determinant, seedgen, slogdet_from_lu,
)
from repro.core.verify import growth_estimate

X64 = bool(jax.config.jax_enable_x64)
needs_x64 = pytest.mark.skipif(
    not X64, reason="compares against a live float64 protocol run"
)

N = 4
#: acceptance bar: f32 relative det error vs f64 references (log space)
F32_DLOG = 1e-4


def _wellcond(n, seed=0, batch=None):
    rng = np.random.default_rng(seed)
    if batch is None:
        return rng.standard_normal((n, n)) + n * np.eye(n)
    return rng.standard_normal((batch, n, n)) + n * np.eye(n)


# ------------------------------------------------------- growth control
def test_growth_safe_cipher_det_relation():
    """The flip-composed cipher still satisfies Decipher's det algebra:
    det(X) = s · det(M) / Ψ with s = growth_safe_sign — for every forced
    rotation degree (seeds drawn until all of k ∈ {1,2,3} are seen)."""
    from repro.core.prt import growth_safe_sign

    seen = set()
    for t in range(24):
        n = 8
        m = _wellcond(n, seed=t)
        seed = seedgen(128, m)
        key = keygen(128, seed, n)
        x, meta = cipher(jnp.asarray(m), key, seed, growth_safe=True)
        seen.add(meta.rotate_k)
        s = growth_safe_sign(n, meta.rotate_k)
        np.testing.assert_allclose(
            np.linalg.det(np.asarray(x)),
            s * np.linalg.det(m) / seed.psi,
            rtol=1e-5,
        )
        assert meta.flipped == (meta.rotate_k % 2 == 1)
    assert seen == {1, 2, 3}


def test_growth_safe_kernel_matches_jnp():
    n = 16
    m = jnp.asarray(_wellcond(n, seed=3))
    seed = seedgen(11, np.asarray(m))
    key = keygen(13, seed, n)
    x_ref, meta = cipher(m, key, seed, growth_safe=True)
    x_k, meta_k = cipher(m, key, seed, growth_safe=True, use_kernel=True)
    np.testing.assert_allclose(np.asarray(x_k), np.asarray(x_ref), rtol=1e-6)
    assert meta == meta_k


def test_growth_safe_tames_element_growth():
    """The headline hazard: an odd rotation of a diagonally dominant
    matrix is anti-diagonally dominant, and the no-pivot LU's growth
    factor explodes (~n). The flip-composed relayout pins it at ~1."""
    from repro.core.lu import lu_nserver
    from repro.core.prt import rotate_degree

    n = 64
    hit = False
    for t in range(12):
        m = _wellcond(n, seed=100 + t)
        seed = seedgen(128, m)
        if rotate_degree(seed.psi) % 2 == 0:
            continue  # only odd rotations exhibit the hazard
        hit = True
        key = keygen(128, seed, n)
        x_unsafe, _ = cipher(jnp.asarray(m), key, seed)
        x_safe, _ = cipher(jnp.asarray(m), key, seed, growth_safe=True)
        xe_unsafe, _ = equilibrate(x_unsafe)
        xe_safe, _ = equilibrate(x_safe)
        lu_g = lu_nserver(xe_unsafe, N)[1]
        lu_s = lu_nserver(xe_safe, N)[1]
        g_unsafe = growth_estimate(lu_g, xe_unsafe)
        g_safe = growth_estimate(lu_s, xe_safe)
        assert g_safe < 4.0, g_safe
        assert g_unsafe > 4 * g_safe, (g_unsafe, g_safe)
    assert hit, "no odd rotation drawn in 12 seeds"


def test_equilibrate_exact_and_det_tracked():
    """Power-of-two scales are lossless: every entry of x_eq is x's entry
    times an exact power of two, and the integer exponent correction
    recovers log|det| exactly (up to the f64 slogdet's own rounding)."""
    x = jnp.asarray(_wellcond(24, seed=7))
    x_eq, log2_scale = equilibrate(x)
    assert np.max(np.abs(np.asarray(x_eq))) <= np.sqrt(2.0) + 1e-9
    assert jnp.issubdtype(log2_scale.dtype, jnp.integer)  # exact, not f32
    s0, l0 = np.linalg.slogdet(np.asarray(x, dtype=np.float64))
    s1, l1 = np.linalg.slogdet(np.asarray(x_eq, dtype=np.float64))
    assert s0 == s1
    # with x64 off the matrices themselves are f32, so the two f64
    # slogdets see slightly different roundings of the same values
    np.testing.assert_allclose(
        l0, l1 - float(log2_scale) * np.log(2.0),
        rtol=1e-12 if X64 else 1e-6,
    )
    # zero matrix: no scaling, no correction, no nan
    z_eq, z_scale = equilibrate(jnp.zeros((5, 5)))
    assert int(z_scale) == 0 and not np.isnan(np.asarray(z_eq)).any()


def test_equilibrate_scales_by_exact_powers_of_two():
    """Rows spanning 2^±60: every x_eq / x ratio is an exact power of two,
    so log|det| is tracked exactly. XLA's exp2 is not exact at every
    integer, so the scales are built from exponent bits."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((48, 48))
                    * np.exp2(rng.integers(-60, 61, (48, 1))))
    x_eq, log2_scale = equilibrate(x)
    k = np.log2(np.abs(np.asarray(x_eq, np.float64)
                       / np.asarray(x, np.float64)))
    np.testing.assert_array_equal(k, np.round(k))
    _, l0 = np.linalg.slogdet(np.asarray(x, dtype=np.float64))
    _, l1 = np.linalg.slogdet(np.asarray(x_eq, dtype=np.float64))
    np.testing.assert_allclose(l0, l1 - float(log2_scale) * np.log(2.0),
                               rtol=1e-12)


def test_compensated_slogdet_pair():
    """float32 factors, float64 log-sum: alternating ±10 logs over
    n = 4096, where a float32 running sum drifts, land on the float64 sum
    of the same diagonal — the reduction runs on the host in float64."""
    n = 4096
    logs = np.where(np.arange(n) % 2 == 0, 10.0, -10.0)
    logs[-1] = 0.125  # make the exact total nonzero
    d = np.exp(logs).astype(np.float32)
    l = jnp.eye(n, dtype=jnp.float32)
    u = jnp.diag(jnp.asarray(d))
    sign, logabs = slogdet_from_lu(l, u)
    want = float(np.sum(np.log(np.abs(d.astype(np.float64)))))
    assert logabs.dtype == np.float64
    assert abs(float(logabs) - want) <= 1e-9, (float(logabs), want)
    assert float(sign) == 1.0


# ------------------------------------------------- f32 protocol end-to-end
@pytest.mark.parametrize("n,servers", [(12, 3), (64, 4), (256, 4)])
def test_f32_roundtrip_matches_f64_reference(n, servers):
    m = _wellcond(n, seed=n)
    want_s, want_la = np.linalg.slogdet(m)
    res = outsource_determinant(m, servers, dtype="float32")
    assert res.verified, res.residual
    assert res.det.sign == want_s
    assert abs(res.det.logabs - want_la) <= F32_DLOG
    assert res.det.dtype == "float32"


def test_f32_batched_roundtrip():
    B, n = 4, 64
    stack = _wellcond(n, seed=1, batch=B)
    res = outsource_determinant(jnp.asarray(stack), N, dtype="float32")
    assert bool(np.all(res.verified))
    for i in range(B):
        ws, wl = np.linalg.slogdet(stack[i])
        assert res.dets[i].sign == ws
        assert abs(res.dets[i].logabs - wl) <= F32_DLOG


def test_f32_mixed_sizes_one_sweep():
    mats = [_wellcond(n, seed=n) for n in (24, 33, 48)]
    res = outsource_determinant(mats, N, dtype="float32")
    assert bool(np.all(res.verified))
    for i, m in enumerate(mats):
        ws, wl = np.linalg.slogdet(m)
        assert res.dets[i].sign == ws
        assert abs(res.dets[i].logabs - wl) <= F32_DLOG


@needs_x64
def test_f32_agrees_with_f64_protocol_run():
    """Property-style agreement: the same matrices through both compute
    dtypes produce Determinants that allclose() at the f32 default
    tolerance — single and batched."""
    for n in (12, 40):
        m = _wellcond(n, seed=n * 3)
        d64 = outsource_determinant(m, N, dtype="float64").det
        d32 = outsource_determinant(m, N, dtype="float32").det
        assert d32.allclose(d64)  # dtype-aware default rtol (1e-4)
        assert not d32.allclose(
            Determinant(d64.sign, d64.logabs + 0.01, d64.dtype)
        )
    stack = _wellcond(32, seed=5, batch=3)
    r64 = outsource_determinant(jnp.asarray(stack), N, dtype="float64")
    r32 = outsource_determinant(jnp.asarray(stack), N, dtype="float32")
    for a, b in zip(r32.dets, r64.dets):
        assert a.allclose(b)


def test_f32_growth_controls_are_defaults_and_overridable():
    m = _wellcond(16, seed=9)
    # f32 auto-enables both; forcing them off still runs (just less robust)
    res = outsource_determinant(m, N, dtype="float32",
                                growth_safe=False, equilibrate=False)
    assert res.det.dtype == "float32"
    # f64 + explicit growth controls works and stays accurate
    if X64:
        want_s, want_la = np.linalg.slogdet(m)
        res = outsource_determinant(m, N, dtype="float64",
                                    growth_safe=True, equilibrate=True)
        assert res.verified and res.det.sign == want_s
        np.testing.assert_allclose(res.det.logabs, want_la, rtol=1e-9)
    # faithful_sign conflicts with the growth-safe relayout
    with pytest.raises(ValueError, match="faithful_sign"):
        outsource_determinant(m, N, dtype="float32", faithful_sign=True)


@pytest.mark.slow
def test_f32_batched_n1024_roundtrip():
    """The acceptance shape the bench guard also pins (BENCH_3.json):
    B×n=1024 f32 stacks stay Q3-verified within the 1e-4 log budget —
    the compensated log accumulation is what keeps the digit."""
    B, n = 2, 1024
    stack = _wellcond(n, seed=10, batch=B)
    res = outsource_determinant(jnp.asarray(stack), N, dtype="float32")
    assert bool(np.all(res.verified))
    for i in range(B):
        ws, wl = np.linalg.slogdet(stack[i])
        assert res.dets[i].sign == ws
        assert abs(res.dets[i].logabs - wl) <= F32_DLOG


def test_f32_distributed_pipeline():
    """The shard_map relay programs are dtype-generic: an f32 stack runs
    the real device pipeline (one mesh device per server) verified."""
    if len(jax.devices()) < N:
        pytest.skip(f"needs {N} devices")
    B, n = 2, 32
    stack = _wellcond(n, seed=11, batch=B)
    res = outsource_determinant(
        jnp.asarray(stack), N, dtype="float32", distributed=True
    )
    assert bool(np.all(res.verified))
    for i in range(B):
        ws, wl = np.linalg.slogdet(stack[i])
        assert res.dets[i].sign == ws
        assert abs(res.dets[i].logabs - wl) <= F32_DLOG


# --------------------------------------------------- f32 verification power
def test_f32_false_reject_rate_is_zero():
    """Honest f32 runs must never be rejected: the growth-aware ε(N)
    absorbs the f32 no-pivot drift (20 trials, mixed rotations)."""
    for t in range(20):
        m = _wellcond(32, seed=500 + t)
        res = outsource_determinant(m, N, dtype="float32")
        assert res.verified, (t, res.residual, res.report.verdict.eps)


@pytest.mark.parametrize("kind,kw", [
    ("dropout", dict(kind="dropout")),
    ("block", dict(mode="block", magnitude=0.5)),
    ("sign_flip_diag", dict(mode="single", magnitude=1.0)),
])
def test_f32_tampered_results_rejected(kind, kw):
    """FA at f32 thresholds: structurally significant tampers (dropout, a
    wholesale strip rescale, a unit-magnitude element hit) are rejected
    for every server. (Detection resolution necessarily scales with the
    compute dtype's noise floor — DESIGN.md §6.3 — so the f32 FA claim is
    pinned at magnitudes above it, unlike the f64 tests' 0.05.)"""
    m = _wellcond(32, seed=77)
    for s in range(N):
        res = outsource_determinant(
            m, N, dtype="float32", faults=ServerFault(server=s, **kw)
        )
        assert not bool(np.all(res.verified)), (kind, s, res.residual)


def test_f32_accepted_results_are_det_accurate():
    """The safety property behind the f32 FA floor: ANY accepted verdict —
    honest or carrying a sub-threshold tamper — yields a determinant
    within the f32 acceptance tolerance of the true one (a tamper small
    enough to pass ε(N) is a backward-stable perturbation)."""
    m = _wellcond(32, seed=88)
    want_s, want_la = np.linalg.slogdet(m)
    accepted = 0
    for s in range(N):
        for t in range(4):
            res = outsource_determinant(
                m, N, dtype="float32",
                faults=ServerFault(server=s, magnitude=1e-4, seed=t),
            )
            if bool(np.all(res.verified)):
                accepted += 1
                assert res.det.sign == want_s
                assert abs(res.det.logabs - want_la) <= 1e-3
    assert accepted > 0  # 1e-4 tampers sit below the f32 noise floor


# ------------------------------------------------------------ f32 recovery
@pytest.mark.parametrize("fault_kw", [
    dict(kind="dropout"),
    dict(mode="block", magnitude=0.5),
])
def test_f32_recovery_under_every_single_server_fault(fault_kw):
    n = 64
    m = _wellcond(n, seed=4)
    want_s, want_la = np.linalg.slogdet(m)
    for s in range(N):
        res = outsource_determinant(
            m, N, dtype="float32",
            faults=ServerFault(server=s, **fault_kw),
            recover=True, standby=1,
        )
        assert bool(np.all(res.verified)) and res.report.recovery.ok, (s, fault_kw)
        assert res.det.sign == want_s
        assert abs(res.det.logabs - want_la) <= F32_DLOG


def test_f32_batched_recovery_splices_one_matrix():
    B, n = 4, 32
    stack = _wellcond(n, seed=6, batch=B)
    res = outsource_determinant(
        jnp.asarray(stack), N, dtype="float32",
        faults=ServerFault(server=2, kind="dropout", matrices=(1,)),
        recover=True, standby=1,
    )
    assert bool(np.all(res.verified)) and res.report.recovery.ok
    for i in range(B):
        ws, wl = np.linalg.slogdet(stack[i])
        assert res.dets[i].sign == ws
        assert abs(res.dets[i].logabs - wl) <= F32_DLOG


# ------------------------------------------------------------- f32 gateway
def test_f32_gateway_bucket_serves_verified():
    from repro.configs import SPDCConfig, SPDCGatewayConfig
    from repro.serve import SPDCGateway

    cfg = SPDCGatewayConfig(
        name="t-f32", buckets=(64,), max_batch=4,
        spdc=SPDCConfig(num_servers=N, dtype="float32"),
    )
    gw = SPDCGateway(cfg)
    mats = [_wellcond(48 + 3 * i, seed=40 + i) for i in range(4)]
    rids = [gw.submit(m) for m in mats]
    for m, rid in zip(mats, rids):
        r = gw.take(rid)
        ws, wl = np.linalg.slogdet(m)
        assert r is not None and r.verified and r.flush_reason == "full"
        assert r.det.dtype == "float32" and r.det.sign == ws
        assert abs(r.det.logabs - wl) <= F32_DLOG
        assert r.batch == 4  # ONE coalesced f32 sweep served all four


@needs_x64
def test_gateway_dtype_override_opens_separate_bucket():
    """f32 and f64 clients must never share a sweep: the dtype rides in
    the BucketKey, so a mixed submission flushes as two sweeps."""
    from repro.configs import SPDCConfig, SPDCGatewayConfig
    from repro.serve import SPDCGateway

    cfg = SPDCGatewayConfig(
        name="t-mixdt", buckets=(32,), max_batch=8,
        spdc=SPDCConfig(num_servers=N),
    )
    gw = SPDCGateway(cfg)
    m = _wellcond(24, seed=3)
    r64 = gw.submit(m)
    r32 = gw.submit(m, dtype="float32")
    gw.drain()
    a, b = gw.take(r64), gw.take(r32)
    assert a.det.dtype == "float64" and b.det.dtype == "float32"
    assert a.batch == 1 and b.batch == 1  # separate buckets, separate sweeps
    assert a.verified and b.verified
    ws, wl = np.linalg.slogdet(m)
    assert abs(a.det.logabs - wl) <= 1e-8
    assert abs(b.det.logabs - wl) <= F32_DLOG
    assert gw.stats.flushes == 2


# --------------------------------------- bugfix regressions (pre-PR fails)
def test_bucket_size_for_synthesizes_when_divisibility_fails():
    """Pre-fix: every bucket failing n' % N == 0 raised NoBucketFits even
    though a valid padded size exists (default power-of-two buckets with
    num_servers=3). Synthesized sizes land on the coarse N·SYNTH_GRID
    grid, not the per-request minimum — see the bounded-compile-set test
    below."""
    from repro.serve.queue import NoBucketFits, bucket_size_for

    assert bucket_size_for(50, (64, 128, 256, 512, 1024), 3) == 96
    assert bucket_size_for(2, (64,), 3) == 48  # servable: 48/3 = 16 > 1
    # a servable configured bucket still wins over synthesis
    assert bucket_size_for(50, (64, 128), 4) == 64
    # genuine oversize still raises → the gateway's direct escape hatch
    with pytest.raises(NoBucketFits):
        bucket_size_for(2000, (64, 128, 256, 512, 1024), 4)
    # synthesis honors the operator's size cap: grid round-up of n=50 is
    # 96 > max(buckets)=64, so the request directs instead of running a
    # sweep larger than any configured bucket
    with pytest.raises(NoBucketFits):
        bucket_size_for(50, (64,), 3)


def test_synthesized_buckets_stay_bounded():
    """Pre-fix (of the fallback itself): each distinct request size
    synthesized its own bucket, so a diverse or adversarial size
    distribution grew the gateway's jit-compile set without bound. The
    grid caps the synthesized sizes at ~max(buckets)/(N·SYNTH_GRID)."""
    from repro.serve.queue import NoBucketFits, SYNTH_GRID, bucket_size_for

    buckets, servers = (64, 128, 256, 512, 1024), 3
    sizes, direct = set(), 0
    for n in range(2, 1025):
        try:
            sizes.add(bucket_size_for(n, buckets, servers))
        except NoBucketFits:
            direct += 1  # grid round-up would exceed max(buckets)
    assert all(s % servers == 0 and s // servers > 1 for s in sizes)
    assert max(sizes) <= max(buckets)  # operator size cap holds
    assert len(sizes) <= 1024 // (servers * SYNTH_GRID) + 1
    # only the thin band above the last grid line under the cap directs
    assert direct < servers * SYNTH_GRID


def test_gateway_submit_override_rides_synthesized_bucket():
    """A num_servers override none of the preset buckets divides must
    still coalesce (pre-fix it silently fell to the direct path)."""
    from repro.configs import SPDCConfig, SPDCGatewayConfig
    from repro.serve import SPDCGateway

    cfg = SPDCGatewayConfig(
        name="t-n3", buckets=(64,), max_batch=2,
        spdc=SPDCConfig(num_servers=4, dtype="float32"),
    )
    gw = SPDCGateway(cfg)
    rids = [gw.submit(_wellcond(20, seed=i), num_servers=3)
            for i in range(2)]
    results = [gw.take(r) for r in rids]
    assert all(r is not None and r.verified for r in results)
    assert results[0].batch == 2  # coalesced, not direct
    assert results[0].pad_to == 48  # synthesized: next N·SYNTH_GRID ≥ 20
    assert gw.stats.direct == 0


def test_gateway_rejects_unservable_preset_bucket():
    """Construction-time validation names the offending bucket."""
    from repro.configs import SPDCConfig, SPDCGatewayConfig
    from repro.serve import SPDCGateway

    with pytest.raises(ValueError, match="129"):
        SPDCGateway(SPDCGatewayConfig(
            name="t-bad", buckets=(64, 129), spdc=SPDCConfig(num_servers=4)
        ))


def test_determinant_allclose_is_relative_det_error():
    """Pre-fix: rtol applied to logabs itself — |Δlog| = 0.5 (a 65%
    relative det error!) passed at rtol=1e-3 once logabs ≈ 1000."""
    a = Determinant(sign=1.0, logabs=1000.0)
    b = Determinant(sign=1.0, logabs=1000.5)
    assert not a.allclose(b, rtol=1e-3)  # pre-fix: True
    # the same |Δlog| near |det| ≈ 1 was and stays a reject
    assert not Determinant(1.0, 0.0).allclose(Determinant(1.0, 0.5),
                                              rtol=1e-3)
    # genuinely close dets pass at any magnitude
    assert a.allclose(Determinant(1.0, 1000.0 + 1e-9), rtol=1e-8)
    # dtype-aware default: an f32-produced det gets the f32 tolerance
    c = Determinant(sign=1.0, logabs=100.0, dtype="float32")
    assert c.allclose(Determinant(1.0, 100.00005, "float32"))
    assert not c.allclose(Determinant(1.0, 100.001, "float32"))


def test_determinant_allclose_zero_and_sign_cases():
    """Pre-fix: sign != sign rejected legitimate det ≈ 0 comparisons."""
    zp = Determinant(sign=1.0, logabs=float("-inf"))
    zn = Determinant(sign=-1.0, logabs=float("-inf"))
    z0 = Determinant(sign=0.0, logabs=float("-inf"))
    assert zp.allclose(zn)  # ±0 are the same determinant (pre-fix: False)
    assert zp.allclose(z0) and z0.allclose(zn)
    one = Determinant(sign=1.0, logabs=0.0)
    assert not zp.allclose(one) and not one.allclose(zn)
    # opposite-sign nonzeros still mismatch
    assert not one.allclose(Determinant(-1.0, 0.0))
    # explicit numeric-zero band: dets below zero_logabs compare as zero
    tiny_p = Determinant(1.0, -700.0)
    tiny_n = Determinant(-1.0, -700.5)
    assert not tiny_p.allclose(tiny_n)
    assert tiny_p.allclose(tiny_n, zero_logabs=-600.0)


def test_determinant_value_raises_instead_of_inf():
    """Pre-fix: .value silently overflowed to inf for log|det| > ~709 —
    any n ≳ 200 ciphered matrix."""
    ok = Determinant(sign=-1.0, logabs=10.0)
    np.testing.assert_allclose(ok.value, -np.exp(10.0))
    big = Determinant(sign=1.0, logabs=800.0)
    with pytest.raises(OverflowError, match="logabs"):
        _ = big.value


# ----------------------------------------------------------- x64-off leg
def test_float64_request_resolves_under_x64_off():
    """With jax.enable_x64 OFF a float64 request must run (as float32)
    instead of warning-per-array or crashing — the gateway default config
    stays usable on every backend."""
    from repro.core import resolve_dtype

    resolved = np.dtype(resolve_dtype("float64"))
    assert resolved == (np.float64 if X64 else np.float32)
    m = _wellcond(16, seed=2)
    res = outsource_determinant(m, N)  # default dtype="float64"
    assert res.verified
    assert res.det.dtype == str(np.dtype(resolved))

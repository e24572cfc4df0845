"""Drive SPDC's served path once on one TPU chip and check every answer.

    python chip_smoke.py             # phases 1-3 on one chip
    python chip_smoke.py --chips4    # phase 4 only, on a four-chip host
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # CPU rehearsal

Everything runs in this one process with x64 off (float32 compute; the
chip has no float64), through the entry points a user calls:

1. Large matrix: the SPDC_DEFAULT shape (n=4096, N=16) and an
   (8, 1024, 1024) stack with N=4 through `outsource_determinant` inline;
   Q3 on every call and Q2 on one; the compiled CED kernel against the
   jnp cipher and once end to end (`use_kernel=True`); a tampering server
   (the default single-entry fault under Q1, a whole-strip fault under Q3)
   rejected, then healed with `recover=True, standby=1`.
2. Gateway: `SPDCGateway(SPDC_GATEWAY_F32)` on the inline transport
   answers 32 seeded requests of mixed size, some repeated.
3. GP step: one jitted `value_and_grad` through `secure_slogdet` +
   `secure_solve` on a seeded RBF kernel matrix (n=1024), as
   examples/gp_loglik.py takes it, on one shared factorization.
4. (--chips4) one n=8192 matrix factored by the shard_map pipeline over
   four chips (N=4), against the inline path on one chip.

Every determinant must be verified and within the float32 budget of the
README's dtype table (|Δ log|det|| ≤ 1e-4, same sign) of numpy's float64
slogdet of the same float32 input. Each phase prints one JSON line with
its wall seconds and the seconds JAX spent tracing, lowering and
compiling; the last line is {"ok": true, "device": {...}} only when every
check passed on a TPU. Any failure raises, and the exit code is not 0.

--tiny shrinks every shape so the same control flow runs on the CPU
(JAX_PLATFORMS=cpu; with XLA_FLAGS=--xla_force_host_platform_device_count=4
for --chips4). A CPU run never reports ok.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from contextlib import contextmanager
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

#: README "Supported dtypes" table: float32 determinants agree with a
#: float64 reference to 1e-4 relative, i.e. 1e-4 in log|det|
LOG_TOL = 1e-4
#: jax.monitoring events whose durations make up a phase's compile time
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class SmokeFailure(AssertionError):
    """A result the chip returned failed its check."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


class Phases:
    """Times each phase: wall seconds and JAX compile seconds within it."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.compile_s += duration

    @contextmanager
    def phase(self, name: str, **fields):
        """Emits the phase's record, with what it measured so far and
        "failed": true when its body raised (the exception propagates)."""
        c0, t0 = self.compile_s, time.perf_counter()
        out: dict = {}
        failed = True
        try:
            yield out
            failed = False
        finally:
            emit({"phase": name, **fields, **out,
                  **({"failed": True} if failed else {}),
                  "wall_s": time.perf_counter() - t0,
                  "compile_s": self.compile_s - c0})


def well_conditioned(rng, shape):
    """float32 inputs of the repo's test family: randn + n·I."""
    import numpy as np

    n = shape[-1]
    return (rng.standard_normal(shape) + n * np.eye(n)).astype(np.float32)


def dlog(det, m32) -> float:
    """|Δ log|det|| against numpy float64 on the same float32 input; the
    sign must match exactly."""
    import numpy as np

    sign, logabs = np.linalg.slogdet(np.asarray(m32, dtype=np.float64))
    check(det.sign == sign, f"sign {det.sign} != numpy {sign}")
    return abs(det.logabs - logabs)


def check_single(res, m32, what: str) -> float:
    check(bool(res.verified), f"{what}: not verified")
    check(res.det.dtype == "float32", f"{what}: computed in {res.det.dtype}")
    d = dlog(res.det, m32)
    check(d <= LOG_TOL, f"{what}: |dlog| {d:.3e} > {LOG_TOL}")
    return d


def check_batch(res, stack, what: str) -> float:
    check(bool(res.verified.all()), f"{what}: {res.verified} not all verified")
    worst = 0.0
    for i, det in enumerate(res.dets):
        check(det.dtype == "float32", f"{what}[{i}]: computed in {det.dtype}")
        worst = max(worst, dlog(det, stack[i]))
    check(worst <= LOG_TOL, f"{what}: |dlog| {worst:.3e} > {LOG_TOL}")
    return worst


# -- phase 1 ---------------------------------------------------------------


def phase_large(ph: Phases, rng, tiny: bool) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import SPDC_DEFAULT
    from repro.core import ServerFault, outsource_determinant
    from repro.core.cipher import _flip_rotated, ewo
    from repro.core.prt import rot90_cw
    from repro.kernels import ops as kops

    n, N = (128, 16) if tiny else (SPDC_DEFAULT.matrix_n,
                                   SPDC_DEFAULT.num_servers)
    m = well_conditioned(rng, (n, n))
    for label, method in (("cold", "q3"), ("warm", "q3"), ("q2", "q2")):
        with ph.phase("large_single", n=n, N=N, method=method,
                      run=label) as out:
            res = outsource_determinant(m, N, dtype="float32", method=method)
            out["max_abs_dlog"] = check_single(res, m, f"n={n} {method}")

    b, n, N = (2, 64, 4) if tiny else (8, 1024, 4)
    stack = well_conditioned(rng, (b, n, n))
    for label in ("cold", "warm"):
        with ph.phase("large_stack", batch=b, n=n, N=N, run=label) as out:
            res = outsource_determinant(stack, N, dtype="float32")
            out["max_abs_dlog"] = check_batch(res, stack, f"({b},{n},{n})")

    # the compiled CED kernel is pure data movement plus one divide: it
    # must match the jnp cipher, at the tile and off it
    with ph.phase("ced_kernel") as out:
        cases, worst = 0, 0.0
        for kn in ((128, 100) if tiny else (1024, 1000)):
            x = jnp.asarray(well_conditioned(rng, (kn, kn)))
            v = jnp.asarray(rng.uniform(0.5, 2.0, kn).astype(np.float32))
            for k in range(4):
                for gs in (False, True):
                    got = kops.ced(x, v, k, growth_safe=gs)
                    want = rot90_cw(ewo(x, v, "ewd"), k)
                    if gs:
                        want = _flip_rotated(want, k)
                    # a wrong layout is off by O(1); the divide may round
                    # differently in Mosaic and XLA by an ulp
                    err = float(jnp.max(jnp.abs(got - want) / jnp.abs(want)))
                    check(err <= 1e-6,
                          f"ced n={kn} k={k} growth_safe={gs}: rel {err:.2e}")
                    worst = max(worst, err)
                    cases += 1
        out.update(cases=cases, max_rel_err=worst)

    n, N = (128, 4) if tiny else (1024, 4)
    m = well_conditioned(rng, (n, n))
    with ph.phase("use_kernel", n=n, N=N) as out:
        res = outsource_determinant(m, N, dtype="float32", use_kernel=True)
        out["max_abs_dlog"] = check_single(res, m, f"use_kernel n={n}")

    # The default fault moves one entry of server 1's reported U off the
    # relay chain: the determinant does not move, so only Q1, which sees
    # every entry of L·U, can reject it (Q3 certifies the diagonal band
    # the determinant reads). The block fault scales server 1's whole U
    # strip by 1.05: the determinant moves, and the default Q3 rejects it.
    for label, fault, method in (
            ("single", ServerFault(server=1, kind="tamper"), "q1"),
            ("block", ServerFault(server=1, kind="tamper", mode="block"),
             "q3")):
        with ph.phase("tamper", n=n, N=N, fault=label, method=method) as out:
            res = outsource_determinant(m, N, dtype="float32", faults=fault,
                                        method=method)
            check(not bool(res.verified), f"{label} tamper was accepted")
            out["rejected"] = True
        with ph.phase("tamper_recover", n=n, N=N, fault=label,
                      method=method) as out:
            res = outsource_determinant(m, N, dtype="float32", faults=fault,
                                        method=method, recover=True,
                                        standby=1)
            rec = res.report.recovery
            check(rec is not None and rec.events
                  and rec.events[0].server == 1,
                  f"{label} tamper: recovery {rec} did not name server 1")
            out["healed"] = [e.server for e in rec.events]
            out["max_abs_dlog"] = check_single(res, m, f"healed {label}")


# -- phase 2 ---------------------------------------------------------------


def phase_gateway(ph: Phases, rng, tiny: bool) -> None:
    from repro.configs import SPDC_GATEWAY_F32
    from repro.serve import SPDCGateway

    sizes = (6, 10, 17, 32, 45, 64) if tiny else (64, 100, 257, 512, 700,
                                                  1024)
    mats = [well_conditioned(rng, (n, n)) for n in rng.choice(sizes, 28)]
    mats += [mats[i] for i in (0, 3, 7, 11)]  # repeats: cache/single-flight
    with ph.phase("gateway", requests=len(mats), sizes=list(sizes)) as out:
        with SPDCGateway(SPDC_GATEWAY_F32) as gw:
            rids = [gw.submit(m) for m in mats]
            gw.drain()
            results = [gw.take(r) for r in rids]
        worst = 0.0
        for r, m in zip(results, mats, strict=True):
            check(r is not None and r.error is None,
                  f"request failed: {r and r.error}")
            check(r.verified, f"request n={r.n} not verified")
            check(r.det.dtype == "float32", f"n={r.n}: {r.det.dtype}")
            worst = max(worst, dlog(r.det, m))
        check(worst <= LOG_TOL, f"gateway |dlog| {worst:.3e} > {LOG_TOL}")
        out["max_abs_dlog"] = worst
        out["coalesced"] = sum(r.flush_reason == "coalesced" for r in results)


# -- phase 3 ---------------------------------------------------------------

#: The GP step is held to float64 numpy at the kernel matrix Σ the device
#: built. log|det Σ| keeps the README budget plus the float32 output's
#: rounding; the fit yᵀΣ⁻¹y, nll and the gradient keep the README's 1e-4
#: (relative to |value|, and to the gradient's largest component). The
#: solution α = Σ⁻¹y itself is held in norm to κ(Σ)·eps(float32), the
#: forward-error bound of a backward-stable float32 solve: 9.6e-4 at
#: n=1024, κ ≈ 8e3. Against Σ built in float64 the device's float32 `exp`
#: moves Σ itself (up to 5e-5 relative), so that comparison is reported,
#: not held.


def gp_reference(x, y, theta, cov=None):
    """float64 numpy (log|det Σ|, yᵀΣ⁻¹y, ∇θ nll) of the GP objective in
    θ = (log ℓ, log σf, log σn):  ∂/∂θᵢ = ½ tr((Σ⁻¹ − ααᵀ) ∂Σ/∂θᵢ).
    `cov` replaces Σ(θ) where given (the matrix the device built)."""
    import numpy as np

    ell, sf, sn = np.exp(theta)
    d2 = (x[:, None] - x[None, :]) ** 2
    k = sf**2 * np.exp(-0.5 * d2 / ell**2)
    if cov is None:
        cov = k + sn**2 * np.eye(len(x))
    inv = np.linalg.inv(cov)
    alpha = inv @ y
    _, logdet = np.linalg.slogdet(cov)
    w = inv - np.outer(alpha, alpha)
    dcov = (k * d2 / ell**2, 2 * k, 2 * sn**2 * np.eye(len(x)))
    return logdet, y @ alpha, np.array([0.5 * np.sum(w * d) for d in dcov])


def phase_gp(ph: Phases, rng, tiny: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.linalg import SecureLinalg, secure_slogdet, secure_solve

    n, N = (64, 2) if tiny else (1024, 2)
    x = np.sort(rng.uniform(-3.0, 3.0, n)).astype(np.float32)
    y = (np.sin(2.0 * x) + 0.5 * x
         + 0.1 * rng.standard_normal(n)).astype(np.float32)
    theta = np.log([0.8, 1.0, 0.2]).astype(np.float32)
    ctx = SecureLinalg(N)
    xj, yj = jnp.asarray(x), jnp.asarray(y)
    const = n * np.log(2 * np.pi)

    def nll(th):
        d2 = (xj[:, None] - xj[None, :]) ** 2
        cov = (jnp.exp(2.0 * th[1]) * jnp.exp(-0.5 * d2 / jnp.exp(2.0 * th[0]))
               + jnp.exp(2.0 * th[2]) * jnp.eye(n))
        _, logdet = secure_slogdet(cov, linalg=ctx)
        alpha = secure_solve(cov, yj, linalg=ctx)
        fit = jnp.dot(yj, alpha, precision=jax.lax.Precision.HIGHEST)
        return 0.5 * (logdet + fit + const), (logdet, fit, alpha)

    def rel(a, b):
        return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))

    with ph.phase("gp_step", n=n, N=N) as out:
        (val, (logdet, fit, alpha)), grad = jax.jit(
            jax.value_and_grad(nll, has_aux=True))(jnp.asarray(theta))
        val, logdet, fit = float(val), float(logdet), float(fit)
        alpha = np.asarray(alpha, dtype=np.float64)
        grad = np.asarray(grad, dtype=np.float64)
        sessions = list(ctx._sessions.values())
        facts = sum(s.factorizations for s in sessions)
        check(facts == 1, f"{facts} factorizations, expected 1")
        # the Σ the secure ops were handed (the session keeps its input)
        cov = np.asarray(sessions[0]._session._m_host, dtype=np.float64)
        args = (x.astype(np.float64), y.astype(np.float64),
                theta.astype(np.float64))
        ref_logdet, ref_fit, ref_grad = gp_reference(*args, cov=cov)
        ref_val = 0.5 * (ref_logdet + ref_fit + const)
        f64_logdet, f64_fit, f64_grad = gp_reference(*args)
        rtol = float(np.linalg.cond(cov)) * float(np.finfo(np.float32).eps)
        ref_alpha = np.linalg.solve(cov, y.astype(np.float64))
        out.update(nll=val, nll_ref=ref_val,
                   abs_dlog=abs(logdet - ref_logdet),
                   alpha_rel_err=float(np.linalg.norm(alpha - ref_alpha)
                                       / np.linalg.norm(ref_alpha)),
                   fit_rel_err=abs(fit - ref_fit) / abs(ref_fit),
                   grad_rel_err=rel(grad, ref_grad), alpha_rtol=rtol,
                   factorizations=facts,
                   nll_f64_sigma=0.5 * (f64_logdet + f64_fit + const),
                   grad_rel_err_f64_sigma=rel(grad, f64_grad))
        check(np.isfinite(val) and np.all(np.isfinite(grad)), "non-finite")
        # the float32 output holds log|det Σ| ≈ -3e3 only to half an ulp
        dlog_tol = LOG_TOL + 0.5 * float(np.spacing(np.float32(
            abs(ref_logdet))))
        check(out["abs_dlog"] <= dlog_tol,
              f"log|det Σ| {logdet} vs float64 {ref_logdet}")
        check(out["alpha_rel_err"] <= rtol,
              f"Σ⁻¹y rel err {out['alpha_rel_err']:.3e} > {rtol:.2e}")
        check(out["fit_rel_err"] <= LOG_TOL,
              f"yᵀΣ⁻¹y {fit} vs float64 {ref_fit}")
        check(abs(val - ref_val) <= LOG_TOL * abs(ref_val),
              f"nll {val} vs float64 {ref_val}")
        check(out["grad_rel_err"] <= LOG_TOL,
              f"gradient rel err {out['grad_rel_err']:.3e} > {LOG_TOL}")


# -- phase 4 ---------------------------------------------------------------


def phase_chips4(ph: Phases, rng, tiny: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import outsource_determinant
    from repro.distrib.spdc_pipeline import lu_nserver_shardmap

    n, N = (256, 4) if tiny else (8192, 4)
    check(len(jax.devices()) >= N, f"need {N} devices, have "
          f"{len(jax.devices())}")
    m = well_conditioned(rng, (n, n))
    with ph.phase("chips4_shardmap", n=n, N=N) as out:
        res = outsource_determinant(m, N, dtype="float32",
                                    transport="shardmap")
        out["max_abs_dlog"] = check_single(res, m, f"shardmap n={n}")
    with ph.phase("chips4_block_rows", n=n, N=N) as out:
        _, u = jax.block_until_ready(lu_nserver_shardmap(jnp.asarray(m), N))
        shards = {s.device.id: s.data.shape for s in u.addressable_shards}
        check(len(shards) == N, f"U lives on {len(shards)} devices, not {N}")
        check(all(s == (n // N, n) for s in shards.values()),
              f"block rows {shards}")
        out["block_rows"] = {str(k): list(v) for k, v in shards.items()}
        # the plain (unciphered) factors: log|det| = Σ log|U_ii|
        diag = np.abs(np.asarray(jnp.diagonal(u), dtype=np.float64))
        ref = np.linalg.slogdet(m.astype(np.float64))[1]
        out["max_abs_dlog"] = abs(float(np.sum(np.log(diag))) - ref)
        check(out["max_abs_dlog"] <= LOG_TOL, f"block-row U: {out}")
    with ph.phase("chips4_inline_one_chip", n=n, N=N) as out:
        res = outsource_determinant(m, N, dtype="float32")
        out["max_abs_dlog"] = check_single(res, m, f"inline n={n}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips4", action="store_true",
                    help="run only the four-chip shard_map phase")
    ap.add_argument("--tiny", action="store_true",
                    help="small shapes, for a CPU rehearsal (never ok)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        emit({"ok": False, "error": f"no repro package under {SRC}"})
        return 2
    sys.path.insert(0, str(SRC))
    import jax
    import numpy as np

    import repro.linalg  # noqa: F401 -- before the backends exist (runtime)
    from repro.runtime import init_process

    init_process(x64=False)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    emit({"device": device})
    if device["platform"] != "tpu" and not args.tiny:
        emit({"ok": False, "error": f"no TPU: JAX found {device}"})
        return 1

    ph = Phases()
    rng = np.random.default_rng(args.seed)
    if args.chips4:
        phase_chips4(ph, rng, args.tiny)
    else:
        phase_large(ph, rng, args.tiny)
        phase_gateway(ph, rng, args.tiny)
        phase_gp(ph, rng, args.tiny)
    if device["platform"] != "tpu":
        emit({"ok": False, "rehearsal": True, "device": device})
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())

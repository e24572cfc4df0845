"""GP marginal log-likelihood through the secure-linalg family.

The intended workload shape for `repro.linalg` (DESIGN.md §12): a
Gaussian-process hyperparameter step needs log|Σ| AND solves against Σ
inside one jitted, grad-ed objective —

    -2·logp(y) = log|Σ(θ)| + yᵀ Σ(θ)⁻¹ y + n·log(2π)

Both terms route through `secure_slogdet` / `secure_solve`: ONE verified
outsourced factorization of Σ per objective evaluation serves the value
and the whole custom-VJP backward pass (∂log|Σ|/∂Σ = Σ⁻ᵀ and the solve
adjoint are triangular-solve rounds through the SAME factors), so the
untrusted fleet does the O(n³) work and the client keeps O(n²) — without
the kernel matrix, the targets, or any gradient crossing the trust
boundary in the clear.

    PYTHONPATH=src python examples/gp_loglik.py [--n 128] [--servers 2]
        [--transport inline] [--gateway]

--gateway additionally serves the same (slogdet, solve) pair through the
SPDC gateway's op-keyed buckets (serve/) to show the service path agrees
with the in-process one.
"""
import argparse

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

# before any jax dispatch: repro.linalg flips jax_cpu_enable_async_dispatch
# at import, which only takes effect while the CPU backend doesn't exist yet
from repro.linalg import SecureLinalg  # noqa: E402
from repro.runtime import init_process


def rbf_cov(x, log_ell, log_sf, log_noise):
    """RBF kernel matrix Σ(θ) on 1-d inputs — differentiable in θ."""
    d2 = (x[:, None] - x[None, :]) ** 2
    k = jnp.exp(2.0 * log_sf) * jnp.exp(-0.5 * d2 / jnp.exp(2.0 * log_ell))
    return k + jnp.exp(2.0 * log_noise) * jnp.eye(x.shape[0])


def make_objectives(x, y, linalg_ctx):
    """(secure, reference) negative log-marginal-likelihood closures."""
    from repro.linalg import secure_slogdet, secure_solve

    n = x.shape[0]

    def nll_secure(theta):
        cov = rbf_cov(x, *theta)
        _, logdet = secure_slogdet(cov, linalg=linalg_ctx)
        alpha = secure_solve(cov, y, linalg=linalg_ctx)
        return 0.5 * (logdet + y @ alpha + n * jnp.log(2.0 * jnp.pi))

    def nll_reference(theta):
        cov = rbf_cov(x, *theta)
        _, logdet = jnp.linalg.slogdet(cov)
        alpha = jnp.linalg.solve(cov, y)
        return 0.5 * (logdet + y @ alpha + n * jnp.log(2.0 * jnp.pi))

    return nll_secure, nll_reference


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=128, help="training points")
    ap.add_argument("--servers", type=int, default=2)
    ap.add_argument("--transport",
                    choices=["inline", "threadpool", "multiprocess",
                             "socket"],
                    default="inline")
    ap.add_argument("--steps", type=int, default=3,
                    help="gradient-descent steps to take")
    ap.add_argument("--gateway", action="store_true",
                    help="also serve the (slogdet, solve) pair through "
                         "the SPDC gateway's op-keyed buckets")
    args = ap.parse_args()
    init_process()

    from repro.api.transport import resolve_transport

    rng = np.random.default_rng(0)
    x = jnp.asarray(np.sort(rng.uniform(-3.0, 3.0, args.n)))
    y_clean = np.sin(2.0 * np.asarray(x)) + 0.5 * np.asarray(x)
    y = jnp.asarray(y_clean + 0.1 * rng.standard_normal(args.n))

    transport = resolve_transport(args.transport)
    ctx = SecureLinalg(args.servers, transport=transport)
    nll_secure, nll_ref = make_objectives(x, y, ctx)

    theta = jnp.asarray([np.log(0.8), np.log(1.0), np.log(0.2)])
    value_and_grad = jax.jit(jax.value_and_grad(nll_secure))
    ref_vg = jax.jit(jax.value_and_grad(nll_ref))

    print(f"GP log-likelihood, n={args.n}, N={args.servers} "
          f"({args.transport} transport)")
    for step in range(args.steps):
        ctx.clear()  # new θ ⇒ new Σ ⇒ new session next evaluation
        val, grad = value_and_grad(theta)
        ref_val, ref_grad = ref_vg(theta)
        gerr = float(jnp.max(jnp.abs(grad - ref_grad))
                     / (jnp.max(jnp.abs(ref_grad)) + 1e-30))
        sessions = list(ctx._sessions.values())
        facts = sum(s.factorizations for s in sessions)
        print(f"  step {step}: nll={float(val):.6f} "
              f"(ref {float(ref_val):.6f}) |grad err|={gerr:.2e} "
              f"factorizations={facts} (sessions={len(sessions)})")
        assert np.isclose(float(val), float(ref_val), rtol=1e-9), \
            "secure nll diverged from the jax.scipy reference"
        assert gerr < 1e-6, f"gradient error {gerr:.2e} exceeds 1e-6"
        assert facts == len(sessions) == 1, \
            "a gradient step must share ONE factorization"
        # normalized step: raw NLL gradients overshoot in log-space
        theta = theta - 0.1 * grad / (jnp.linalg.norm(grad) + 1.0)
    print("OK: value and gradient match the plaintext reference; each "
          "step used one shared verified LU.")

    if args.gateway:
        from repro.configs.spdc import SPDC_GATEWAY_DEFAULT
        from repro.serve.spdc_gateway import SPDCGateway

        cov = np.asarray(rbf_cov(x, *theta))
        # kernel matrices need the growth-safe relayout (the reason it is
        # the LinalgSession default): no-pivot LU growth on a near-SPD Σ
        # overflows the verifier otherwise. It is a bucket dimension, so
        # the override rides the submit call.
        with SPDCGateway(SPDC_GATEWAY_DEFAULT) as gw:
            r_sl = gw.submit(cov, op="slogdet", growth_safe=True)
            r_sv = gw.submit(cov, op="solve", rhs=np.asarray(y),
                             growth_safe=True)
            gw.drain()
            sl, sv = gw.take(r_sl), gw.take(r_sv)
        ws, wl = np.linalg.slogdet(cov)
        alpha = np.linalg.solve(cov, np.asarray(y))
        assert sl.verified and sl.sign == ws and \
            np.isclose(sl.logabs, wl, rtol=1e-9)
        serr = float(np.linalg.norm(np.asarray(sv.solution) - alpha)
                     / np.linalg.norm(alpha))
        assert sv.verified and serr < 1e-8, serr
        print(f"OK: gateway op-keyed buckets agree "
              f"(slogdet bucket + solve bucket, solve err {serr:.2e}).")


if __name__ == "__main__":
    main()

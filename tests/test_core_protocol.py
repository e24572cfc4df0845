"""SPDC protocol: seed/key/cipher/augment/LU/verify/decipher, unit +
end-to-end + hypothesis property tests."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    augment, cipher, keygen, lu_blocked, lu_nserver, lu_unblocked,
    outsource_determinant, padding_for_servers, q1, q2, q3,
    q3_paper_literal, seedgen, slogdet_from_lu,
)


def _wellcond(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, n)) + n * np.eye(n)


# ---------------------------------------------------------------------- seed
def test_seedgen_deterministic_and_sensitive():
    m = _wellcond(8)
    s1 = seedgen(128, m)
    s2 = seedgen(128, m)
    assert s1.psi == s2.psi and s1.digest == s2.digest
    s3 = seedgen(129, m)  # different λ → different seed
    assert s3.psi != s1.psi
    m2 = m.copy(); m2[0, 0] += 1.0  # different stats → different seed
    assert seedgen(128, m2).psi != s1.psi
    assert 2**-4 <= s1.psi <= 2**4


# ---------------------------------------------------------------------- key
@settings(max_examples=20, deadline=None)
@given(n=st.integers(2, 64))
def test_keygen_product_constraint(n):
    seed = seedgen(128, _wellcond(max(n, 2)))
    key = keygen(128, seed, n)
    assert key.v.shape == (n,)
    assert not np.any(key.v == 1.0)  # paper constraint v_i != 1
    np.testing.assert_allclose(np.prod(key.v), seed.psi, rtol=1e-9)


@pytest.mark.parametrize("n", [64, 1024])
def test_keygen_last_entry_stays_in_band(n):
    """v_n absorbs the product constraint without leaving the band the
    others are drawn from; were it left to absorb their random walk (~9
    bits at n=1024), κ(V⁻¹M) would grow by that factor."""
    seed = seedgen(128, _wellcond(n))
    v = keygen(128, seed, n).v
    off = np.log2(v / float(seed.psi) ** (1.0 / n))
    # spread 0.5, plus the centring shift (the mean offset, ~0.29/√n)
    assert np.max(np.abs(off)) <= 0.75


# -------------------------------------------------------------------- cipher
@pytest.mark.parametrize("mode", ["ewd", "ewm"])
def test_cipher_det_relation(mode):
    """det(X) = s · det(M) · Ψ^{∓1} — the relation Decipher inverts."""
    n = 8
    m = jnp.asarray(_wellcond(n))
    seed = seedgen(128, np.asarray(m))
    key = keygen(128, seed, n)
    x, meta = cipher(m, key, seed, mode=mode)
    from repro.core.prt import rotation_sign

    s = rotation_sign(n, meta.rotate_k)
    det_m = np.linalg.det(np.asarray(m))
    det_x = np.linalg.det(np.asarray(x))
    if mode == "ewd":
        np.testing.assert_allclose(det_x, s * det_m / seed.psi, rtol=1e-9)
    else:
        np.testing.assert_allclose(det_x, s * det_m * seed.psi, rtol=1e-9)


def test_cipher_kernel_path_matches_jnp():
    n = 16
    m = jnp.asarray(_wellcond(n))
    seed = seedgen(7, np.asarray(m))
    key = keygen(9, seed, n)
    x_ref, _ = cipher(m, key, seed, use_kernel=False)
    x_k, _ = cipher(m, key, seed, use_kernel=True)
    np.testing.assert_allclose(np.asarray(x_k), np.asarray(x_ref), rtol=1e-12)


def test_cipher_hides_entries():
    """Ciphertext should not reveal plaintext entries (basic sanity — each
    entry is scaled by a secret v_i and relocated)."""
    n = 12
    m = jnp.asarray(_wellcond(n))
    seed = seedgen(128, np.asarray(m))
    key = keygen(128, seed, n)
    x, _ = cipher(m, key, seed)
    assert not np.allclose(np.sort(np.asarray(x).ravel()),
                           np.sort(np.asarray(m).ravel()))


# ------------------------------------------------------------------- augment
@settings(max_examples=30, deadline=None)
@given(n=st.integers(2, 40), servers=st.integers(1, 8))
def test_padding_rule(n, servers):
    p = padding_for_servers(n, servers)
    assert (n + p) % servers == 0 and (n + p) // servers > 1
    # minimality
    for q in range(p):
        assert (n + q) % servers != 0 or (n + q) // servers <= 1


def test_paper_examples_of_augmentation():
    assert padding_for_servers(4, 3) == 2  # paper example 1: 4×4, N=3 → 6×6
    assert padding_for_servers(6, 2) == 0  # paper example 2: 6×6, N=2 → p=0


@settings(max_examples=20, deadline=None)
@given(n=st.integers(2, 16), p=st.integers(0, 5))
def test_augment_preserves_det(n, p):
    import jax

    a = jnp.asarray(_wellcond(n, seed=n + p))
    b = augment(a, p, key=jax.random.key(0))
    np.testing.assert_allclose(
        np.linalg.det(np.asarray(b)), np.linalg.det(np.asarray(a)), rtol=1e-9
    )


# ------------------------------------------------------------------------ LU
@pytest.mark.parametrize("n", [4, 16, 33])
def test_lu_unblocked(n):
    a = jnp.asarray(_wellcond(n))
    l, u = lu_unblocked(a)
    np.testing.assert_allclose(np.asarray(l @ u), np.asarray(a), atol=1e-9)
    assert np.allclose(np.diag(np.asarray(l)), 1.0)
    assert np.allclose(np.asarray(l), np.tril(np.asarray(l)))
    assert np.allclose(np.asarray(u), np.triu(np.asarray(u)))


@pytest.mark.parametrize("n,block", [(16, 4), (32, 8), (64, 16)])
def test_lu_blocked_matches_unblocked(n, block):
    a = jnp.asarray(_wellcond(n))
    l1, u1 = lu_unblocked(a)
    l2, u2 = lu_blocked(a, block)
    np.testing.assert_allclose(np.asarray(l2), np.asarray(l1), atol=1e-9)
    np.testing.assert_allclose(np.asarray(u2), np.asarray(u1), atol=1e-9)


@pytest.mark.parametrize("n,servers", [(8, 2), (12, 3), (16, 4), (30, 5)])
def test_lu_nserver_matches_and_logs_comm(n, servers):
    a = jnp.asarray(_wellcond(n))
    l, u, log = lu_nserver(a, servers)
    np.testing.assert_allclose(np.asarray(l @ u), np.asarray(a), atol=1e-8)
    # one-way chain: exactly N-1 messages, each to the next server
    assert log.hops == servers - 1
    assert all(dst == src + 1 for src, dst, _ in log.messages)
    s, la = slogdet_from_lu(l, u)
    want_s, want_la = np.linalg.slogdet(np.asarray(a))
    assert float(s) == want_s
    np.testing.assert_allclose(float(la), want_la, rtol=1e-9)


# ------------------------------------------------------------------- verify
def test_q_formulas_zero_on_correct_lu():
    n = 16
    a = jnp.asarray(_wellcond(n))
    l, u = lu_unblocked(a)
    r = jnp.asarray(np.random.default_rng(0).standard_normal(n))
    assert float(jnp.max(jnp.abs(q1(l, u, a, r)))) < 1e-9
    assert abs(float(q2(l, u, a, r))) < 1e-8
    assert float(q3(l, u, a)) < 1e-10
    assert float(q3_paper_literal(l, u, a)) < 1e-10


def test_q_formulas_reject_tampering():
    n = 16
    a = jnp.asarray(_wellcond(n))
    l, u = lu_unblocked(a)
    u_bad = u.at[3, 3].multiply(1.01)
    r = jnp.asarray(np.random.default_rng(0).standard_normal(n))
    assert abs(float(q2(l, u_bad, a, r))) > 1e-4
    assert float(q3(l, u_bad, a)) > 1e-4


def test_q3_literal_cancellation_weakness():
    """The paper's literal Q3 (abs outside the sum) accepts a tampering
    whose per-row errors cancel — the per-element form rejects it.
    (DESIGN.md §1.1 erratum.)"""
    n = 8
    a = jnp.asarray(_wellcond(n))
    l, u = lu_unblocked(a)
    # equal-and-opposite diagonal perturbations
    u_bad = u.at[0, 0].add(0.5)
    u_bad = u_bad.at[1, 1].add(-0.5 * float(l[0, 0] / l[1, 1]))
    lit = float(q3_paper_literal(l, u_bad, a))
    strict = float(q3(l, u_bad, a))
    assert strict > 0.1          # real check catches it
    assert lit < strict / 100    # literal form nearly blind to it


def test_q3_growth_widening_is_not_attacker_inflatable():
    """Adaptive attack on the growth-widened ε: plant a pair of huge
    strictly-upper entries in U whose diagonal contributions cancel
    (L[i,j]·Δ + L[i,j']·δ = 0) — Q3's residual is untouched while
    max|U| (hence growth_estimate, hence ε) inflates by ~1e8 — then bias
    a diagonal entry by far more than the honest tolerance. Pre-fix,
    authenticate(method="q3") accepted the biased determinant; the
    q3_growth_cap clamp must reject it.
    """
    from repro.core.verify import (
        authenticate, epsilon, growth_estimate, q3_growth_cap,
    )

    n, servers = 32, 4
    a = jnp.asarray(_wellcond(n))
    l, u = lu_unblocked(a)
    assert authenticate(l, u, a, num_servers=servers, method="q3").ok

    # cancelling pair in column n-1: Δ·L[i,0] + δ·L[i,1] = 0
    i = n - 1
    scale = 1e8 * float(jnp.max(jnp.abs(a))) / float(jnp.abs(l[i, 1]))
    u_adv = u.at[0, i].add(float(l[i, 1]) * scale)
    u_adv = u_adv.at[1, i].add(-float(l[i, 0]) * scale)
    inflation = growth_estimate(u_adv, a) / growth_estimate(u, a)
    assert inflation > 1e6  # the planted entries dominate max|U|

    # diagonal bias: residual ≈ |U[k,k]|·τ sits far above the clamped ε
    # but far below the raw growth-widened ε the pre-fix code used
    base_eps = epsilon(servers, n, a, dtype=a.dtype)
    k = 3
    tau = 100.0 * base_eps * q3_growth_cap(n) / abs(float(u[k, k]))
    u_adv = u_adv.at[k, k].multiply(1.0 + tau)

    verdict = authenticate(l, u_adv, a, num_servers=servers, method="q3")
    assert verdict.residual < base_eps * growth_estimate(u_adv, a)
    assert not verdict.ok  # pre-fix: accepted (ok == residual <= raw ε)
    # the secret-probed Q1 form sees the planted entries outright
    rng = np.random.default_rng(7)
    assert not authenticate(
        l, u_adv, a, num_servers=servers, method="q1", rng=rng
    ).ok


# ------------------------------------------------------------ end-to-end
@pytest.mark.parametrize("mode", ["ewd", "ewm"])
@pytest.mark.parametrize("method", ["q1", "q2", "q3"])
def test_protocol_roundtrip(mode, method):
    m = _wellcond(12, seed=5)
    res = outsource_determinant(m, 3, mode=mode, method=method)
    want_s, want_la = np.linalg.slogdet(m)
    assert res.verified, f"residual {res.residual}"
    assert res.det.sign == want_s
    np.testing.assert_allclose(res.det.logabs, want_la, rtol=1e-9)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(4, 24), servers=st.integers(2, 5),
       mode=st.sampled_from(["ewd", "ewm"]))
def test_protocol_property(n, servers, mode):
    m = _wellcond(n, seed=n * 7 + servers)
    res = outsource_determinant(m, servers, mode=mode)
    want_s, want_la = np.linalg.slogdet(m)
    assert res.verified
    assert res.det.sign == want_s
    np.testing.assert_allclose(res.det.logabs, want_la, rtol=1e-8)


def test_protocol_detects_malicious_server():
    m = _wellcond(12, seed=9)
    res = outsource_determinant(
        m, 3, tamper=lambda l, u: (l.at[5, 2].add(0.05), u)
    )
    assert not res.verified


def test_protocol_faithful_sign_differs_for_n_mod4_0():
    """Same run deciphered with the paper's literal sign vs the theorem's:
    they disagree exactly when n ≡ 0,1 (mod 4) and an odd rotation fired."""
    for seed in range(12):
        m = _wellcond(8, seed=seed)  # n = 8 ≡ 0 (mod 4)
        res = outsource_determinant(m, 2)
        if res.meta.rotate_k % 2 == 1:
            res_paper = outsource_determinant(m, 2, faithful_sign=True)
            assert res_paper.det.sign == -res.det.sign
            want_s, _ = np.linalg.slogdet(m)
            assert res.det.sign == want_s  # the corrected one is right
            return
    pytest.skip("no odd rotation drawn in 12 seeds")


def test_protocol_with_augmentation_and_odd_sizes():
    """Paper Table III: odd sizes supported via minimal padding."""
    for n, servers in [(7, 2), (9, 4), (11, 3)]:
        m = _wellcond(n, seed=n)
        res = outsource_determinant(m, servers)
        assert res.padding == padding_for_servers(n, servers)
        want_s, want_la = np.linalg.slogdet(m)
        assert res.verified and res.det.sign == want_s
        np.testing.assert_allclose(res.det.logabs, want_la, rtol=1e-8)


# ---------------------------------------------------------------- inversion
def test_secure_inverse_roundtrip():
    """Beyond-paper (paper §VII.B future work): secure outsourced INVERSION
    on the same CED+LU machinery; client recovery is O(n²)."""
    from repro.core import outsource_inverse

    rng = np.random.default_rng(5)
    for n, servers, mode in [(12, 3, "ewd"), (16, 4, "ewm"), (9, 2, "ewd")]:
        m = rng.standard_normal((n, n)) + n * np.eye(n)
        res = outsource_inverse(m, servers, mode=mode)
        assert res.verified, res.residual
        np.testing.assert_allclose(
            np.asarray(res.inverse) @ m, np.eye(n), atol=1e-8
        )


def test_secure_inverse_rejects_tampering():
    from repro.core import outsource_inverse

    rng = np.random.default_rng(6)
    m = rng.standard_normal((12, 12)) + 12 * np.eye(12)
    res = outsource_inverse(m, 3, tamper=lambda iv: iv.at[3, 4].add(0.01))
    assert not res.verified


@settings(max_examples=10, deadline=None)
@given(n=st.integers(4, 20), servers=st.integers(2, 4),
       mode=st.sampled_from(["ewd", "ewm"]))
def test_secure_inverse_property(n, servers, mode):
    from repro.core import outsource_inverse

    rng = np.random.default_rng(n * 13 + servers)
    m = rng.standard_normal((n, n)) + n * np.eye(n)
    res = outsource_inverse(m, servers, mode=mode)
    assert res.verified
    np.testing.assert_allclose(np.asarray(res.inverse) @ m, np.eye(n), atol=1e-7)

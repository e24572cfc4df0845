"""`correct` comes out false when the timed path is broken underneath.

Each test drives the rest of a run (the harness without its look for a
chip) on a small copy of a cell, with the cell's own limits: first sound,
then once for each fault the cell can have. A served answer altered where
it is produced, for every cell; the exchange between chips left out, for
the shard_map cell; and the control, the program's products one precision
step down.
"""
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness, reference

FAMILY = {"family": "lowrank_shift", "alpha": 0.5, "gamma": 1.0, "rank": 16}
SPEC = harness.load_spec()


def small_cell(kind: str):
    """(workload, configuration, mix, limits): a small copy of a real cell."""
    if kind == "gateway":
        _, cfg, _, limits = harness.resolve(SPEC, "gw_small")
        cfg = dict(cfg, gateway_changes={"buckets": [64], "max_batch": 4})
        mix = {"loop": "closed", "clients": 4, "sizes": {"dist": "uniform", "lo": 16, "hi": 64},
               "matrices": FAMILY, "check": {"sample": None}}
    else:
        # the shard_map copy runs testdata/spdc_pod4.json, the configuration
        # of a four-chip cell not yet measured, under large_n4096's mix and limit
        _, cfg, mix, limits = harness.resolve(SPEC, "large_n4096")
        if kind == "shardmap":
            cfg = json.loads((harness.BENCH / "testdata" / "spdc_pod4.json").read_text())
        cfg = dict(cfg, spdc_changes=dict(cfg["spdc_changes"], matrix_n=64, num_servers=4))
        mix = dict(mix, check={"sample": None})
    wl = {"name": f"small_{kind}", "chips": 1}
    return wl, json.loads(json.dumps(cfg)), mix, limits


def run_small(kind: str, seed: int = 2**33 + 5, seconds: float | None = None) -> dict:
    """One run of a small cell; a protocol cell's window holds one answer."""
    cell = small_cell(kind)
    seconds = seconds or (0.5 if kind == "gateway" else 0.1)
    line = harness.run_cell(cell[0]["name"], seed, seconds, False,
                            t_process=time.monotonic(), require_tpu=False, cell=cell)
    line.pop("_run")
    return line


KINDS = ["gateway", "inline", "shardmap"]


@pytest.mark.parametrize("kind", KINDS)
def test_a_sound_run_is_correct(kind, jax_settings):
    line = run_small(kind)
    assert line["correct"], line["checks"]
    assert line["checks"]["compared"]["value"] >= 1


@pytest.mark.parametrize("kind", KINDS)
def test_an_answer_altered_where_it_is_produced_is_caught(kind, jax_settings, monkeypatch):
    import repro.api.client as client

    limit = small_cell(kind)[3]["max_dlogdet"]

    def nudged(fn):
        def wrapper(*args, **kwargs):
            out = fn(*args, **kwargs)
            bump = lambda d: dataclasses.replace(d, logabs=d.logabs + 2 * limit)  # noqa: E731
            return [bump(d) for d in out] if isinstance(out, list) else bump(out)
        return wrapper

    monkeypatch.setattr(client, "decipher", nudged(client.decipher))
    monkeypatch.setattr(client, "decipher_batch", nudged(client.decipher_batch))
    line = run_small(kind)
    assert not line["correct"]
    assert line["checks"]["max_dlogdet"]["value"] > limit


def test_the_exchange_between_chips_left_out_is_caught(jax_settings, monkeypatch):
    from repro.distrib import spdc_pipeline

    assert len(jax.devices()) >= 4
    monkeypatch.setattr(jax.lax, "ppermute", lambda x, axis_name, perm: x)
    spdc_pipeline._compiled_pipeline.cache_clear()
    try:
        line = run_small("shardmap")
    finally:
        spdc_pipeline._compiled_pipeline.cache_clear()
        jax.clear_caches()  # no later test may reuse the broken programs
    assert not line["correct"], line["checks"]


def one_pass_bf16(a, b):
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def test_the_control_fails_the_limit(jax_settings, monkeypatch):
    """The control on the chip is the program at Precision.HIGH; XLA:CPU
    computes every float32 product in full whatever the precision says,
    so here the LU's products are made one bfloat16 pass each, which on
    the CPU reads as the chip's HIGH did (PERF.md, the control's readings)."""
    import repro.core.lu as lu

    monkeypatch.setattr(lu, "precise_matmul", one_pass_bf16)
    jax.clear_caches()  # trace the sweep again with the patched products
    try:
        line = run_small("gateway")
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not line["correct"]
    assert line["checks"]["max_dlogdet"]["value"] > small_cell("gateway")[3]["max_dlogdet"]


def test_reference_sample_keeps_the_largest_and_is_seeded():
    from bench.record import Req

    reqs = [Req(idx=i, n=10 + i % 7, due=0.0) for i in range(50)]
    a = reference.sample(reqs, 3, 10)
    b = reference.sample(reqs, 3, 10)
    assert [r.idx for r in a] == [r.idx for r in b] and len(a) == 10
    assert max(r.n for r in a) == 16
    assert reference.sample(reqs, 3, None) == reqs

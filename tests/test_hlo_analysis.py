"""Loop-aware HLO analysis: trip-count multiplication of flops/collectives."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_analysis import analyze


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_scan_flops_multiplied_by_trip_count():
    def body(x, w):
        return x @ w, None

    def scanned(x, ws):
        x, _ = jax.lax.scan(body, x, ws)
        return x.sum()

    x = jnp.zeros((64, 64))
    ws = jnp.zeros((7, 64, 64))
    a = analyze(_compile(scanned, x, ws))
    assert a["flops"] == pytest.approx(7 * 2 * 64**3, rel=0.01)


def test_nested_scan():
    def body(x, w):
        return x @ w, None

    def inner(x, ws):
        x, _ = jax.lax.scan(body, x, ws)
        return x, None

    def nested(x, ws):
        x, _ = jax.lax.scan(inner, x, ws)
        return x.sum()

    x = jnp.zeros((32, 32))
    ws = jnp.zeros((3, 5, 32, 32))
    a = analyze(_compile(nested, x, ws))
    assert a["flops"] == pytest.approx(15 * 2 * 32**3, rel=0.01)


def test_unrolled_matches_raw_cost_analysis():
    def unrolled(x, ws):
        for i in range(4):
            x = x @ ws[i]
        return x.sum()

    x = jnp.zeros((48, 48))
    ws = jnp.zeros((4, 48, 48))
    compiled = jax.jit(unrolled).lower(x, ws).compile()
    a = analyze(compiled.as_text())
    raw = compiled.cost_analysis().get("flops", 0)
    assert a["flops"] == pytest.approx(raw, rel=0.05)


def test_traffic_positive_and_collectives_empty_on_one_device():
    def f(x):
        return (x @ x).sum()

    a = analyze(_compile(f, jnp.zeros((128, 128))))
    assert a["traffic_bytes"] > 128 * 128 * 4
    assert a["total_collective_bytes"] == 0

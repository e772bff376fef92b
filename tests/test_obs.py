"""The program's instrumentation (repro.obs): every named scope reaches the
compiled train step's op_name metadata and changes nothing else in it, and
the prefetch producer's span reaches both a profiler trace and the span log."""
import contextlib
import dataclasses
import glob
import os
import re

import jax
import pytest

from repro import obs
from repro.backend import Backend
from repro.configs import get_smoke
from repro.data import IndexedPackedDataset, lm_batches, markov_documents, write_token_cache
from repro.train import init_state, make_train_step

_METADATA = re.compile(r", metadata=\{[^}]*\}")


def _compiled_step_text():
    """HLO text of a tiny VR-LAMB train step on the fused plan (Pallas in
    interpret mode), k = 2 microbatches, layers scanned and rematerialized."""
    cfg = get_smoke("granite-3-2b").replace(global_batch=4, seq_len=32)
    cfg = cfg.replace(
        optimizer=dataclasses.replace(cfg.optimizer, name="vr_lamb", k=2),
        parallel=dataclasses.replace(cfg.parallel, backend=Backend.all_fused(), remat=True),
    )
    step_fn, _ = make_train_step(cfg)
    state = init_state(cfg)
    batch = next(lm_batches(cfg.model.vocab_size, cfg.global_batch, cfg.seq_len, seed=0))
    step = jax.jit(step_fn)
    return step.lower(state, batch).compile().as_text()


@pytest.fixture(scope="module")
def step_text():
    return _compiled_step_text()


def test_every_scope_is_in_the_compiled_fused_step(step_text):
    op_names = set(re.findall(r'op_name="([^"]*)"', step_text))
    for scope in obs.SCOPES:
        pattern = re.compile(r"(^|/)(\w+\()*" + scope + r"\)*(/|$)")
        assert any(pattern.search(n) for n in op_names), scope
    model = [n for n in op_names if "(model)" in n]
    assert any("transpose(" in n for n in model)
    assert any("rematted_computation" in n for n in model)


def test_scopes_add_metadata_and_nothing_else(step_text, monkeypatch):
    monkeypatch.setattr(obs, "scope", lambda name: contextlib.nullcontext())
    bare = _compiled_step_text()

    def instructions(text):
        return [_METADATA.sub("", line) for line in text.splitlines()
                if " = " in line and not line.lstrip().startswith(("HloModule", "FileNames"))]

    assert instructions(step_text) == instructions(bare)
    assert "stats_pack" not in bare and "stats_pack" in step_text


def test_the_producer_span_reaches_the_trace_and_the_log(tmp_path):
    cache = str(tmp_path / "cache")
    write_token_cache(markov_documents(64, 4000, 3, 70, seed=0), cache, vocab=64)
    ds = IndexedPackedDataset(cache, 32, 4, seed=0)
    before = len(obs.traced_durations(obs.DATA_PRODUCE))
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        it = ds.iter_batches(device=True, prefetch_size=2)
        batches = [next(it), next(it)]
        it.close()
    finally:
        jax.profiler.stop_trace()
    assert all(isinstance(b["tokens"], jax.Array) for b in batches)
    logged = obs.traced_durations(obs.DATA_PRODUCE)[before:]
    (path,) = glob.glob(os.path.join(tmp_path, "trace", "**", "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    traced = [e.duration_ns for p in data.planes if p.name.startswith("/host:")
              for line in p.lines for e in line.events if e.name == obs.DATA_PRODUCE]
    # the producer runs up to three batches ahead of the two consumed
    assert 2 <= len(traced) == len(logged) <= 2 + 3
    assert all(d > 0 for d in logged)


def test_a_span_is_logged_only_while_a_trace_records(tmp_path):
    before = len(obs.traced_durations(obs.DATA_PRODUCE))
    with obs.span(obs.DATA_PRODUCE):
        pass
    assert len(obs.traced_durations(obs.DATA_PRODUCE)) == before
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            with obs.span(obs.DATA_PRODUCE):
                pass
    finally:
        jax.profiler.stop_trace()
    got = obs.traced_durations(obs.DATA_PRODUCE)[before:]
    assert len(got) == 3 and all(d >= 0 for d in got)
    assert all(name.startswith("repro.") for name in obs.SPANS)

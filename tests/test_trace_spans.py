"""Profiler spans of the serve path (``repro.serving.trace``), on the CPU.

A small config is served through ``MagnusService``, ``PagedContinuousEngine``
and ``drive_paged`` under ``jax.profiler.trace``; the recorded xplane is
read back with ``ProfileData``.  The traffic shares two instruction
templates, so the radix cache serves part of every later prompt.  The
profiler starts inside the module fixture, never at import, so every
pytest worker collects the same tests.  No timing is asserted.
"""
import copy
import glob
import os

import jax
import pytest

from repro.core.magnus import MagnusConfig, MagnusService
from repro.core.predictor import GenerationLengthPredictor
from repro.launch.serve import _pool_memory_model
from repro.models import model as M
from repro.serving import trace
from repro.serving.engine import PagedContinuousEngine, drive_paged
from repro.serving.paged_cache import BlockAllocator
from repro.workload.apps import make_dataset, make_shared_prefix_dataset
from repro.workload.tokenizer import encode

from conftest import tiny_engine_cfg

CFG = tiny_engine_cfg()
NUM_BLOCKS, BLOCK_TOKENS, MAX_LEN, MAX_GEN = 96, 4, 64, 8
SPANS = ("magnus.predict", "magnus.batch", "magnus.schedule",
         "engine.admit", "engine.prefill_wave", "radix.publish",
         "engine.window", "engine.grow", "engine.decode", "engine.readback",
         "engine.retire")
WINDOW_CHILDREN = ("engine.grow", "engine.decode", "engine.readback",
                   "engine.retire")


def _requests():
    # 14-word instructions end mid-block at 4 tokens a block: later
    # requests of a template hit its published pages
    reqs = make_shared_prefix_dataset(10, n_apps=2, instr_words=14,
                                      input_words=5, seed=11)
    for i, r in enumerate(reqs):
        r.gen_length = 2 + (i * 3) % 7
    return reqs


def _serve(params, predictor, reqs):
    """Every request handed to the service, then served to the end."""
    memory = _pool_memory_model(CFG, NUM_BLOCKS * BLOCK_TOKENS, 4,
                                max_len=MAX_LEN, max_gen=MAX_GEN)
    allocator = BlockAllocator(NUM_BLOCKS, BLOCK_TOKENS)
    svc = MagnusService(memory, MagnusConfig(strategy="magnus-paged",
                                             prefix_sharing=True),
                        predictor=predictor, allocator=allocator)
    engine = PagedContinuousEngine(CFG, params=params, max_concurrency=4,
                                   max_len=MAX_LEN, max_gen=MAX_GEN,
                                   allocator=allocator,
                                   prefix_cache=svc.prefix_cache)
    for r in reqs:
        svc.on_request(r, 0.0)

    def refill(steps):
        b = svc.next_batch(float(steps))
        return b.requests if b is not None else None

    stats = drive_paged(engine, [], refill=refill,
                        backlog=lambda: len(svc.batcher.queue) > 0)
    assert stats["served"] == len(reqs)
    engine.assert_drained()
    return engine


def _read_spans(trace_dir):
    """The program's spans: (name, line, start_ns, end_ns, attrs)."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.split(".", 1)[0] in ("magnus", "engine", "radix"):
                    out.append((e.name, line.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    params = M.init_params(CFG, jax.random.PRNGKey(0))
    predictor = GenerationLengthPredictor(seed=0).fit(make_dataset(40,
                                                                  seed=1))
    reqs = _requests()
    untraced = _serve(params, predictor, copy.deepcopy(reqs))
    assert not trace.recording()
    tdir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(tdir):
        assert trace.recording()
        traced = _serve(params, predictor, copy.deepcopy(reqs))
    return {"reqs": reqs, "traced": traced, "untraced": untraced,
            "spans": _read_spans(tdir)}


def _named(spans, name):
    return [s for s in spans if s[0] == name]


@pytest.mark.parametrize("name", SPANS)
def test_every_span_is_recorded(served, name):
    assert _named(served["spans"], name), f"no {name} span in the trace"


@pytest.mark.parametrize("name", WINDOW_CHILDREN)
def test_window_work_nests_in_engine_window(served, name):
    windows = _named(served["spans"], "engine.window")
    for child, line, start, end, _ in _named(served["spans"], name):
        assert any(w[1] == line and w[2] <= start and end <= w[3]
                   for w in windows), f"{child} at {start} outside a window"


def test_window_attrs_count_the_decode(served):
    """Each decoding window names its fused steps and rows; the steps
    sum to the engine's decode steps."""
    windows = [s[4] for s in _named(served["spans"], "engine.window")
               if "k" in s[4]]
    assert windows and all(w["rows"] >= 1 for w in windows)
    assert sum(w["k"] for w in windows) == served["traced"].decode_steps
    decodes = [s[4]["k"] for s in _named(served["spans"], "engine.decode")]
    assert sum(decodes) == served["traced"].decode_steps


def test_decode_attrs_count_the_kernel_pages(served):
    """Each decode dispatch names the paged kernel's pages a step, as the
    kernel picks them for this pool, and the live pages of its rows: at
    least one a row, at most a full table a row."""
    from repro.kernels.decode_attention.kernel import decode_pages_per_step
    eng = served["traced"]
    kp = eng.pages["k"]
    want = decode_pages_per_step(
        kp.shape[2] * kp.shape[3] * kp.shape[4] * kp.dtype.itemsize,
        eng.max_blocks)
    windows = [s for s in _named(served["spans"], "engine.window")
               if "k" in s[4]]
    decodes = _named(served["spans"], "engine.decode")
    assert len(decodes) == len(windows)
    for win, dec in zip(windows, decodes):
        attrs, rows = dec[4], win[4]["rows"]
        assert attrs["pages_per_step"] == want
        assert rows <= attrs["live_pages"] <= rows * eng.max_blocks


def test_schedule_spans_name_each_batch(served):
    """Each scheduling pass names the queue it chose from and whether the
    serving-time estimator behind HRRN was fit; the batches chosen hold
    every request."""
    picks = [s[4] for s in _named(served["spans"], "magnus.schedule")]
    assert all("queued" in p and "estimator_fit" in p for p in picks)
    assert sum(p.get("size", 0) for p in picks) == len(served["reqs"])


def test_predict_spans_name_the_requests_served(served):
    ids = {s[4]["req_id"] for s in _named(served["spans"], "magnus.predict")}
    assert ids == {r.req_id for r in served["reqs"]}
    assert ids == set(served["traced"].generated)
    batched = {s[4]["req_id"] for s in _named(served["spans"],
                                               "magnus.batch")}
    assert batched == ids


def test_wave_attrs_match_the_radix_cache(served):
    """The cached tokens the waves report are the prompt tokens the radix
    cache served instead of a prefill, and the waves' rows are the
    requests admitted."""
    eng = served["traced"]
    waves = [s[4] for s in _named(served["spans"], "engine.prefill_wave")]
    prompt = sum(len(encode(f"{r.instruction} {r.user_input}",
                            CFG.vocab_size)[:MAX_LEN])
                 for r in served["reqs"])
    cached = sum(w["cached_tokens"] for w in waves)
    assert eng.prefix_cache.hits > 0 and cached > 0
    assert cached == prompt - eng.prefill_tokens
    assert sum(w["suffix_tokens"] for w in waves) == eng.prefill_tokens
    assert sum(w["rows"] for w in waves) == len(served["reqs"])
    ids = [int(i) for w in waves
           for i in str(w["req_ids"]).strip("[]").split(",")]
    assert sorted(ids) == sorted(r.req_id for r in served["reqs"])


def test_profiler_leaves_generation_unchanged(served):
    assert served["traced"].generated == served["untraced"].generated
    assert served["traced"].host_syncs == served["untraced"].host_syncs

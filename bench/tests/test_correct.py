"""``correct`` comes out false when the timed path is broken underneath,
once for each fault a one-chip serving cell can have, and the control
(the reference in a precision below the configuration's) reads well above
what the program reads."""
import jax
import jax.numpy as jnp
import pytest

import arch
import generator as G
import tiny
import weights as W
from repro.serving import engine as E


def _wrap_decode(monkeypatch, fault):
    """Break the engine's fused decode program as ``fault`` says."""
    orig_init = E.PagedContinuousEngine.__init__

    def init(self, *a, **kw):
        orig_init(self, *a, **kw)
        real = self._decode_multi

        def broken(params, *, pages, batch, num_steps):
            if fault == "state_unchanged":
                keep = jax.tree.map(jnp.copy, pages)
                logits, _, pos, toks = real(params, pages=pages, batch=batch,
                                            num_steps=num_steps)
                return logits, keep, pos, toks
            if fault == "half_batch":
                act = batch["active"]
                half = act & (jnp.arange(act.shape[0]) % 2 == 0)
                return real(params, pages=pages,
                            batch=dict(batch, active=half),
                            num_steps=num_steps)
            if fault == "token_altered":
                logits, pages, pos, toks = real(params, pages=pages,
                                                batch=batch,
                                                num_steps=num_steps)
                return logits, pages, pos, (toks + 1) % self.cfg.vocab_size
            raise ValueError(fault)

        self._decode_multi = broken

    monkeypatch.setattr(E.PagedContinuousEngine, "__init__", init)


def test_sound_run_is_correct():
    out = tiny.run()
    assert out["correct"], out["checks"]
    assert out["checks"]["max_logit_gap"]["value"] <= tiny.TINY_LIMIT


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_fault_is_not_correct(monkeypatch, fault):
    _wrap_decode(monkeypatch, fault)
    out = tiny.run()
    assert not out["correct"], (fault, out["checks"])
    assert out["checks"]["max_logit_gap"]["value"] > tiny.TINY_LIMIT


def test_drain_cap_fails_the_run():
    """Requests still unfinished when the drain cap passes are failed,
    and the run is not correct (the pool is not drained either)."""
    out = tiny.run(drain_cap_s=0.0, rate=40.0)
    assert not out["correct"]
    assert out["failed"] > 0 and out["checks"]["pool_leak"]["value"] == 1


def test_control_reads_above_the_program():
    """The fp8 control, put in the program's place on the streams the
    bf16 engine served, reads at least three times the program's gap."""
    bench, w, conf, mix, peak, dev = tiny.cell()
    from repro.core.types import Request
    A = arch.of(conf)
    sv = conf["serving"]
    seed = 11
    reqs = G.arrivals(mix, seed, [0.0, 2.0], max_len=sv["max_len"],
                      max_gen=sv["max_gen"], make=Request)[:6]
    params = W.make_params(seed, conf, jnp.bfloat16)
    import run as RUN
    eng = E.PagedContinuousEngine(
        A.program_config(conf), params, max_concurrency=sv["slots"],
        num_blocks=sv["num_blocks"], max_len=sv["max_len"],
        max_gen=sv["max_gen"], dtype=jnp.bfloat16)
    for r in reqs:
        r.gen_length = r.predicted_gen_length = sv["max_gen"]
    assert eng.join_many(reqs) == len(reqs)
    while eng.num_active:
        eng.step_window()
    seqs = [(A.prompt_ids(r, conf, sv["max_len"]), eng.generated[r.req_id])
            for r in reqs]
    out = A.served_gaps(seed, conf, seqs, control="fp8")
    program, control = float(out["gap"].max()), float(
        out["control_gap"].max())
    assert control >= 3 * program, (program, control)
    # judged as a run is judged, the control in the program's place fails
    assert RUN.judge(program, tiny.TINY_LIMIT, 0, 0, "")[0]
    assert not RUN.judge(control, tiny.TINY_LIMIT, 0, 0, "")[0]


def test_overload_run_is_correct():
    """Above capacity the requests admitted in the window count; they are
    drained after it and judged like any other."""
    out = tiny.run(traffic="lmaas-overload", rate=60.0)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and "throughput_tok_s" in out["metrics"]


def test_engine_without_wave_hook_is_refused():
    import driver

    class NoWaves:
        pass

    with pytest.raises(TypeError):
        driver.timed_engine_class(NoWaves)

"""Open-loop LMaaS traffic from a mix file and a seed.

One general generator reads every traffic mix (``bench/traffic/<mix>.json``):

- ``task_table``: the name of a task table in ``bench/traffic/tasks/``,
  which holds ``tasks`` (each an instruction template and a
  generation-length model ``gen = slope * UIL + intercept`` times the
  input's verbosity register and a lognormal noise; the paper's 8-task
  mix is copied from the program's ``workload/apps.py``), ``registers``
  (verbosity registers: marker words planted in the input, a length
  multiplier, a share) and ``words``;
- ``template``: ``{"kind": "plain"}`` sends the instruction as it is;
  ``{"kind": "fewshot", "tokens": n}`` puts an ``n``-word few-shot
  preamble, drawn per task from the seed, before it;
- ``arrivals``: ``{"process": "poisson", "rate": r}`` in requests/s.

The sizes (task, input length, register, generation length) and the
inter-arrival gaps of each phase of a run are drawn once from
``SIZES_SEED``; the run's ``--seed`` only orders them and draws the words
and the weights.  Every seed therefore offers the same work.

Prompts are ``BOS + template + instruction + input`` word tokens, cut at
the configuration's ``max_len``; generations are cut at ``max_gen``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Callable, List, Optional

import numpy as np

BOS_ID = 1
N_SPECIAL = 3
SIZES_SEED = 20240607
TRAFFIC = os.path.dirname(os.path.abspath(__file__))


def encode(text: str, vocab_size: int) -> List[int]:
    """Word-hash token ids, as the program's word tokenizer makes them:
    BOS, then each whitespace-separated word hashed (blake2b, 4 bytes,
    little-endian) into ``[3, vocab_size)``."""
    ids = [BOS_ID]
    for w in text.split():
        h = hashlib.blake2b(w.encode(), digest_size=4).digest()
        ids.append(N_SPECIAL + int.from_bytes(h, "little")
                   % (vocab_size - N_SPECIAL))
    return ids


@dataclasses.dataclass
class Spec:
    """The size of one request, independent of the run's seed."""
    task: int
    uil: int
    register: int
    gen: int


def load_mix(name: str) -> dict:
    """``bench/traffic/<name>.json`` with its task table merged in."""
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        mix = json.load(f)
    with open(os.path.join(TRAFFIC, "tasks", mix["task_table"] + ".json")) \
            as f:
        table = json.load(f)
    return {**mix, **{k: table[k] for k in ("tasks", "registers", "words")}}


def _draw_spec(mix: dict, rng: np.random.Generator, max_gen: int,
               task: Optional[int] = None) -> Spec:
    tasks, regs = mix["tasks"], mix["registers"]
    ti = int(rng.integers(len(tasks))) if task is None else task
    t = tasks[ti]
    uil = int(rng.integers(*t["uil_range"]))
    ri = int(rng.choice(len(regs), p=[r["share"] for r in regs]))
    gen = (t["slope"] * uil + t["intercept"]) * regs[ri]["mult"]
    gen *= float(np.exp(rng.normal(0.0, t["noise_frac"])))
    return Spec(ti, uil, ri, int(np.clip(round(gen), 1, max_gen)))


def templates(mix: dict, seed: int) -> List[str]:
    """Each task's instruction as sent: with a few-shot template, an
    ``n``-word preamble drawn from ``seed`` comes first."""
    tpl = mix["template"]
    instr = [t["instruction"] for t in mix["tasks"]]
    if tpl["kind"] == "plain":
        return instr
    if tpl["kind"] != "fewshot":
        raise ValueError(f"unknown template kind {tpl['kind']!r}")
    rng = np.random.default_rng([seed, 0x7E3])
    words = mix["words"].split()
    return [" ".join(rng.choice(words, size=tpl["tokens"])) + " " + i
            for i in instr]


def _realize(mix: dict, spec: Spec, instruction: str,
             rng: np.random.Generator, max_len: int, make: Callable):
    """A request of ``spec``'s sizes with words drawn from ``rng``."""
    t = mix["tasks"][spec.task]
    markers = mix["registers"][spec.register]["markers"]
    words = list(rng.choice(mix["words"].split(), size=spec.uil))
    for mk in markers:
        for _ in range(max(2, spec.uil // 15)):
            words[int(rng.integers(0, spec.uil))] = mk
    length = min(1 + len(instruction.split()) + spec.uil, max_len)
    return make(app=t["app"], task=t["task"], instruction=instruction,
                user_input=" ".join(words), length=length,
                user_input_length=spec.uil, gen_length=spec.gen)


def arrivals(mix: dict, seed: int, phases: List[float], *, max_len: int,
             max_gen: int, make: Callable) -> List:
    """Requests due over consecutive phases (pre-roll, window, tail) of the
    given durations, with ``arrival_time`` set (seconds from the start of
    traffic) and ``req_id`` numbered in order of arrival.

    Each phase holds a fixed number of arrivals, ``rate x duration``, and
    a fixed set of sizes and gaps drawn from ``SIZES_SEED``, the gaps scaled
    to fill the phase.  The run's ``seed`` permutes the sizes and the gaps
    within each phase and draws the words: every seed offers the window
    the same work, in another order.  ``make`` builds a request from
    keyword fields."""
    arr = mix["arrivals"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    rate = float(arr["rate"])
    rng = np.random.default_rng([seed, 0xA77])
    instr = templates(mix, seed)
    out, start = [], 0.0
    for i, dur in enumerate(phases):
        n = int(round(rate * dur))
        fixed = np.random.default_rng([SIZES_SEED, i])
        specs = [_draw_spec(mix, fixed, max_gen) for _ in range(n)]
        gaps = fixed.exponential(1.0 / rate, size=n + 1)
        gaps = gaps[rng.permutation(n + 1)] * (dur / gaps.sum())
        t = start
        for gap, j in zip(gaps, rng.permutation(n)):
            t += float(gap)
            r = _realize(mix, specs[j], instr[specs[j].task], rng, max_len,
                         make)
            r.arrival_time = t
            r.req_id = len(out)
            out.append(r)
        start += dur
    return out


def training_set(mix: dict, seed: int, per_task: int, *, max_len: int,
                 max_gen: int, make: Callable) -> List:
    """``per_task`` requests of each task drawn afresh from ``seed``:
    the data the length predictor is fitted on before serving."""
    rng = np.random.default_rng([seed, 0x7A1])
    instr = templates(mix, seed)
    out = []
    for ti in range(len(mix["tasks"])):
        for _ in range(per_task):
            spec = _draw_spec(mix, rng, max_gen, task=ti)
            out.append(_realize(mix, spec, instr[ti], rng, max_len, make))
    return out


def prompt_ids(req, vocab_size: int, max_len: int) -> List[int]:
    """The token ids of ``req``'s prompt as the engine is sent them."""
    return encode(f"{req.instruction} {req.user_input}", vocab_size)[:max_len]

"""The generator: every seed offers the same work in the window, in
another order; the tokenizer copy matches the program's."""

import generator as G
from repro.core.types import Request
from repro.workload.tokenizer import encode


def _mix(name="lmaas-steady"):
    return G.load_mix(name)


def test_same_work_in_another_order():
    mix = _mix()
    a = G.arrivals(mix, 1, [30, 51, 60], max_len=512, max_gen=1024,
                   make=Request)
    b = G.arrivals(mix, 2 ** 33 + 9, [30, 51, 60], max_len=512,
                   max_gen=1024, make=Request)
    def window(rs):
        return sorted((r.task, r.gen_length, r.user_input_length)
                      for r in rs if 30 <= r.arrival_time < 81)
    assert window(a) == window(b)
    assert [r.gen_length for r in a] != [r.gen_length for r in b]
    assert all(x.arrival_time < y.arrival_time for x, y in zip(a, a[1:]))


def test_tokenizer_matches_the_program():
    mix = _mix("lmaas-fewshot-steady")
    for r in G.arrivals(mix, 5, [5.0], max_len=512, max_gen=1024,
                        make=Request)[:8]:
        text = f"{r.instruction} {r.user_input}"
        assert G.encode(text, 49152) == encode(text, 49152)
        assert len(r.instruction.split()) >= 128

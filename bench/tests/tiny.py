"""A tiny cell for CPU tests: the smollm-135m configuration and the
lmaas-steady mix, shrunk so a whole run takes seconds on the CPU."""
import generator as G
import run as RUN

# a bf16 engine at this size reads a widest gap of about 2e-3 against the
# float32 reference (CPU); broken paths read 0.1 and more
TINY_LIMIT = 0.02


def cell(traffic: str = "lmaas-steady", rate: float = 10.0,
         drain_cap_s: float = 60.0, reference: str = ""):
    """The tiny cell; ``reference`` names another architecture module for
    its configuration."""
    bench = RUN.load_json(RUN.ROOT, "BENCHMARK.json")
    conf = RUN.load_json(RUN.BENCH, "configs", "smollm-135m.json")
    if reference:
        conf["reference"] = reference
    conf.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
                vocab_size=512)
    conf["serving"].update(slots=8, max_len=64, max_gen=64, num_blocks=512)
    conf["check"]["max_logit_gap"] = TINY_LIMIT
    mix = G.load_mix(traffic)
    mix.update(preroll_s=1.0, drain_cap_s=drain_cap_s)
    mix["arrivals"]["rate"] = rate
    if mix["template"]["kind"] == "fewshot":
        mix["template"]["tokens"] = 32
    w = next(x for x in bench["workloads"] if x["traffic"] == traffic)
    peak = RUN.load_json(RUN.BENCH, "peaks.json")["TPU v5 lite"]
    dev = {"platform": "cpu", "kind": "cpu", "count": 1}
    return bench, w, conf, mix, peak, dev


def run(seed: int = 2 ** 33 + 7, seconds: float = 2.0, trace: bool = False,
        **kw):
    bench, w, conf, mix, peak, dev = cell(**kw)
    RUN.setup_jax(require_tpu=False, chips=1)
    return RUN.run_cell(bench, w, conf, mix, peak, seed, seconds, trace, dev)

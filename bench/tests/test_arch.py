"""The architecture module a configuration names (``bench/arch.py``).

The dense module must give what the harness gave before the Llama facts
moved into it (``fixtures/dense_invariants.json``, recorded from that
harness): the same ModelConfig, the same weights bit for bit, the same
operation and byte counts.  An architecture added as a file alone is
served and judged; an unknown one fails at load."""
import dataclasses
import functools
import hashlib
import json
import os
import re
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import arch
import flops as F
import run as RUN
import tiny
import weights as W

HERE = os.path.dirname(os.path.abspath(__file__))
LLAMA_KEYS = ("intermediate_size", "rms_norm_eps", "rope_theta",
              "num_key_value_heads", "tie_word_embeddings", "head_dim")


@functools.lru_cache(maxsize=None)
def parent() -> dict:
    with open(os.path.join(HERE, "fixtures", "dense_invariants.json")) as f:
        return json.load(f)


def conf_of(which: str) -> dict:
    if which == "tiny":
        return tiny.cell()[2]
    return RUN.load_json(RUN.BENCH, "configs", "smollm-135m.json")


def digest(params) -> str:
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(x)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("which", ["smollm", "tiny"])
def test_program_config_is_unchanged(which):
    conf = conf_of(which)
    got = dataclasses.asdict(arch.of(conf).program_config(conf))
    want = parent()["program_config"][which]
    for field, value in want.items():
        assert got[field] == value, field


@pytest.mark.parametrize("tied", [True, False])
def test_weights_are_unchanged(tied):
    conf = conf_of("tiny")
    conf["tie_word_embeddings"] = tied
    params = W.make_params(parent()["seed"], conf, jnp.bfloat16)
    assert digest(params) == \
        parent()["weights_sha256"]["tied" if tied else "untied"]


def test_full_size_layout_is_unchanged():
    conf = conf_of("smollm")
    shapes = jax.eval_shape(
        lambda: W.make_params(parent()["seed"], conf, jnp.bfloat16))
    got = [[jax.tree_util.keystr(p), list(x.shape), str(x.dtype)]
           for p, x in jax.tree_util.tree_flatten_with_path(shapes)[0]]
    assert got == parent()["shapes_smollm"]


@pytest.mark.parametrize("which", ["smollm", "tiny"])
def test_decode_window_counts_are_unchanged(which):
    conf = conf_of(which)
    for args, want in parent()["decode_window"][which]:
        assert F.decode_window(conf, *args) == want, args
    assert F.decode_window(conf, 4, 16, 3000, b=4) == \
        parent()["decode_window_b4"][which]


@pytest.mark.parametrize("which", ["smollm", "tiny"])
def test_prefill_wave_counts_are_unchanged(which):
    conf = conf_of(which)
    for rows, want in parent()["prefill_wave"][which]:
        assert F.prefill_wave(conf, [tuple(r) for r in rows]) == want, rows
    rows = [tuple(r) for r in parent()["prefill_wave"][which][1][0]]
    assert F.prefill_wave(conf, rows, b=4) == \
        parent()["prefill_wave_b4"][which]


def test_architecture_added_as_a_file_is_served(monkeypatch, tmp_path):
    """A copy of the dense module under another name, in a reference
    directory of its own, is taken by name alone: the tiny cell is
    served through it and judged correct."""
    shutil.copy(os.path.join(RUN.BENCH, "reference", "dense.py"),
                tmp_path / "llama_copy.py")
    monkeypatch.setattr(arch, "REFERENCE", str(tmp_path))
    conf = tiny.cell(reference="llama_copy")[2]
    assert arch.of(conf).__file__ == str(tmp_path / "llama_copy.py")
    with pytest.raises(FileNotFoundError):
        arch.load("dense")          # only the copy can be found
    out = tiny.run(reference="llama_copy")
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0
    assert out["checks"]["max_logit_gap"]["value"] <= tiny.TINY_LIMIT


@pytest.mark.parametrize("name, body, error, words", [
    ("no_such_arch", None, FileNotFoundError, "no_such_arch.py"),
    ("../dense", None, ValueError, "not an identifier"),
    ("half_arch", "def program_config(m):\n    return None\n", ImportError,
     "lacks num_layers, layer_shapes"),
])
def test_unknown_architecture_fails_at_load(monkeypatch, tmp_path, name,
                                            body, error, words):
    monkeypatch.setattr(arch, "REFERENCE", str(tmp_path))
    if body is not None:
        (tmp_path / f"{name}.py").write_text(body)
    with pytest.raises(error, match=re.escape(words)):
        arch.of({"reference": name})
    if error is FileNotFoundError:
        with pytest.raises(error, match=re.escape(str(tmp_path))):
            W.make_params(1, dict(conf_of("tiny"), reference=name),
                          jnp.bfloat16)


def test_harness_reads_no_architecture_key():
    """Every architecture fact lives in ``bench/reference/``."""
    for sub in ("", "metrics", "traffic"):
        d = os.path.join(RUN.BENCH, sub)
        for name in sorted(os.listdir(d)):
            if not name.endswith(".py"):
                continue
            with open(os.path.join(d, name)) as f:
                text = f.read()
            found = [k for k in LLAMA_KEYS if k in text]
            assert not found, (os.path.join(sub, name), found)

"""The architecture module a configuration file names.

A configuration's ``"reference"`` key names ``bench/reference/<name>.py``.
That module holds every fact of its architecture the harness needs, so
adding an architecture means adding that file and a configuration, and
nothing else here changes.  Its hooks, each given the configuration
file's dict ``conf``:

- ``program_config(conf)``: the program's ``ModelConfig``;
- ``num_layers(conf)``, ``layer_shapes(conf)`` and ``top_shapes(conf)``:
  the parameter layout, as trees of ``weights.Leaf`` (shape and first
  draw) for one layer and for the leaves outside the layers; the seed
  derivation in ``weights.py`` is shared;
- ``served_gaps(seed, conf, seqs, control=None)``: the plain reference's
  comparison of served greedy tokens;
- ``decode_window(conf, k, rows, ctx, b)`` and
  ``prefill_wave(conf, rows, b)``: operation and byte counts (``flops.py``);
- ``prompt_ids(req, conf, max_len)``: the token ids a request's prompt is
  put to the model as.
"""
from __future__ import annotations

import functools
import importlib.util
import os

REFERENCE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "reference")
HOOKS = ("program_config", "num_layers", "layer_shapes", "top_shapes",
         "served_gaps", "decode_window", "prefill_wave", "prompt_ids")


@functools.lru_cache(maxsize=None)
def _load_file(path: str):
    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location("arch_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [h for h in HOOKS if not callable(getattr(mod, h, None))]
    if missing:
        raise ImportError(f"architecture {path} lacks {', '.join(missing)}")
    return mod


def load(name: str):
    """``<REFERENCE>/<name>.py``, checked for every hook."""
    if not name.isidentifier():
        raise ValueError(f"architecture name {name!r} is not an identifier")
    path = os.path.join(REFERENCE, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no architecture {name!r}: {path} does not "
                                "exist")
    return _load_file(path)


def of(conf: dict):
    """The architecture module of the configuration ``conf``."""
    return load(conf["reference"])

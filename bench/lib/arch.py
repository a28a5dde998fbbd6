"""A configuration's architecture module, found by its published ``model_type``.

Everything in the benchmark that knows one architecture's layout lives in
``bench/models/<model_type>.py``, found here from the configuration file's
own ``model_type`` (as the source's ``config.json`` states it).  A module
gives:

* ``shapes(c)``: ``{leaf path: (shape, dtype name)}`` of the weights, and
  ``ZEROS``: the leaf names that start at zero (norm gains);
* ``model_config(c)``, ``to_program(w)`` and ``canonical(paths)``: the
  program's ``ModelConfig``, the weights in the program's parameter tree, and
  program leaf paths mapped back onto the weights' paths;
* ``reference_view(c)`` and ``REFERENCE``: the keys its plain reference
  reads, and that reference's file name under ``bench/reference/``.  A
  reference gives ``features``, ``logits`` and ``loss``, each taking
  ``precision`` ``"f32"`` or ``"fp8"``, and imports nothing of the program;
* FLOP and byte counts for the metric readers, where it has them:
  ``flops_per_token(c, seq_len)``, ``call_bounds(c, tokens)`` and
  ``tick_bounds(c, live, context, prompts)``.  A reader whose count the
  module lacks reads nothing.

A configuration of a new architecture adds its module, its reference, its
configuration, traffic and limits files; no file here changes.
"""

from __future__ import annotations

import functools
import pathlib

from bench.lib import spec

ROOT = pathlib.Path(__file__).resolve().parents[2]


@functools.lru_cache(maxsize=None)
def _load(root: pathlib.Path, folder: str, name: str):
    path = root / spec.BENCH_DIR / folder / f"{spec._check_name(name)}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {folder} module for {name!r}: {path} is missing")
    return spec.load_module(path, f"{folder}_{name}")


def of(c: dict, root: pathlib.Path = ROOT):
    """The architecture module of configuration ``c``:
    ``<root>/bench/models/<model_type>.py``, loaded once per process."""
    return _load(pathlib.Path(root).resolve(), "models", c["model_type"])


def reference(c: dict, root: pathlib.Path = ROOT):
    """The plain reference that ``c``'s architecture module names."""
    return _load(pathlib.Path(root).resolve(), "reference", of(c, root).REFERENCE)


def count(c: dict, name: str, root: pathlib.Path = ROOT):
    """The architecture's count ``name`` (a function), or None where its
    module has none."""
    return getattr(of(c, root), name, None)

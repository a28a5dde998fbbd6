"""The system under test, reached through its public entry points only.

Maps a configuration file onto the program's optimizer, hands the program
the bench's own weights (``bench/lib/weights.py``) in its parameter layout,
and builds the ``Trainer`` or ``ServeEngine`` a cell drives.  The
configuration's ``ModelConfig`` and the program's parameter layout come from
its architecture module (``bench/lib/arch.py``), whose ``model_config`` is the
only other place the benchmark imports the program (``src/repro``).
"""

from __future__ import annotations

import pathlib
import sys


def import_program(root: pathlib.Path) -> None:
    src = str(pathlib.Path(root) / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def optimizer(c: dict):
    from repro.optim.adamw import AdamWConfig

    o = c["optimizer"]
    return AdamWConfig(
        lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"], clip_norm=o["clip_norm"],
        moment_dtype=o["moment_dtype"], warmup_steps=o["warmup_steps"],
        total_steps=o["total_steps"],
    )


def _same_layout(got, want) -> None:
    import jax

    if jax.tree.structure(got) != jax.tree.structure(want):
        raise ValueError("the bench's weights do not match the program's parameter tree")
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"weight {a.shape}/{a.dtype} where the program has "
                             f"{b.shape}/{b.dtype}")


def trainer(model, c: dict, w: dict, *, total_steps: int):
    """A one-chip ``Trainer`` for ``c`` whose parameters are the bench's
    weights ``w``; ``model`` is ``c``'s architecture module."""
    import jax

    from repro.parallel.sharding import make_plan
    from repro.train.trainer import Trainer, TrainerConfig

    tcfg = TrainerConfig(total_steps=total_steps)
    tr = Trainer(model.model_config(c), optimizer(c), tcfg, make_plan(None))
    params = model.to_program(w)
    _same_layout(params, tr.params)
    tr.params = params
    jax.block_until_ready(tr.params)
    return tr


def train_one_step(tr, batches) -> dict:
    """One step through ``Trainer.train``, which returns after the step's
    metrics reached the host."""
    tr.tcfg.total_steps = tr.step + 1
    return tr.train(batches)[-1]


def serve_engine(model, c: dict, w: dict, *, slots: int, max_len: int):
    """A ``ServeEngine`` over the bench's weights ``w`` (greedy decoding);
    ``model`` is ``c``'s architecture module.  The K/V cache is paged unless
    the configuration's ``system`` sets ``paged`` false."""
    from repro.parallel.sharding import make_plan
    from repro.serve.engine import ServeConfig, ServeEngine

    s = c["system"]
    scfg = ServeConfig(
        slots=slots, max_len=max_len, paged=s.get("paged", True), page_size=s["page_size"],
        prefix_cache=s["prefix_cache"], sample=False,
    )
    return ServeEngine(model.to_program(w), model.model_config(c), make_plan(None), scfg)


def request(rid: int, prompt, max_new: int):
    from repro.serve.batching import Request

    return Request(rid=rid, prompt=prompt, max_new_tokens=int(max_new))


def program_spans():
    """The program's own tracer (``repro.obs.trace``)."""
    from repro.obs import trace

    return trace


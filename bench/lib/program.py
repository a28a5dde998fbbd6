"""The system under test, reached through its public entry points only.

Maps a configuration file onto the program's ``ModelConfig`` and optimizer,
hands it the bench's own weights (``bench/lib/weights.py``), and builds the
``Trainer`` or ``ServeEngine`` a cell drives.  This is the one file of the
benchmark that imports the program (``src/repro``).
"""

from __future__ import annotations

import pathlib
import sys

from bench.lib import dims


def import_program(root: pathlib.Path) -> None:
    src = str(pathlib.Path(root) / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def model_config(c: dict):
    """The program's ``ModelConfig`` for configuration file ``c``.

    The router's auxiliary losses stay out of the objective: the source
    config sets ``output_router_logits`` false, so its
    ``router_aux_loss_coef`` is not applied, and Mixtral has no z-loss."""
    from repro.models.config import ModelConfig, MoEConfig

    s = c["system"]
    return ModelConfig(
        name=c["name"],
        family="moe",
        num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        head_dim=dims.head_dim(c),
        moe=MoEConfig(
            num_experts=c["num_local_experts"],
            top_k=c["num_experts_per_tok"],
            d_ff=c["intermediate_size"],
            backend=s["moe_backend"],
            dispatch=s["dispatch"],
            decode_backend=s["decode_backend"],
            balance_loss=c["router_aux_loss_coef"] if c["output_router_logits"] else 0.0,
            router_z_loss=0.0,
        ),
        act=c["hidden_act"],
        dtype=c["torch_dtype"],
        rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        remat=s["remat"],
        opt_moment_dtype=c.get("optimizer", {}).get("moment_dtype", "float32"),
    )


def optimizer(c: dict):
    from repro.optim.adamw import AdamWConfig

    o = c["optimizer"]
    return AdamWConfig(
        lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"], clip_norm=o["clip_norm"],
        moment_dtype=o["moment_dtype"], warmup_steps=o["warmup_steps"],
        total_steps=o["total_steps"],
    )


def to_program(w: dict) -> dict:
    """The bench's weight tree in the program's parameter layout (the same
    leaves, nested as ``init_model`` nests them: one scanned block kind)."""
    return {
        "embed": w["embed"],
        "blocks": {"0_global": w["layers"]},
        "final_norm": w["final_norm"],
        "lm_head": w["lm_head"],
    }


def _same_layout(got, want) -> None:
    import jax

    if jax.tree.structure(got) != jax.tree.structure(want):
        raise ValueError("the bench's weights do not match the program's parameter tree")
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"weight {a.shape}/{a.dtype} where the program has "
                             f"{b.shape}/{b.dtype}")


def trainer(c: dict, w: dict, *, total_steps: int):
    """A one-chip ``Trainer`` for ``c`` whose parameters are the bench's
    weights ``w``."""
    import jax

    from repro.parallel.sharding import make_plan
    from repro.train.trainer import Trainer, TrainerConfig

    tcfg = TrainerConfig(total_steps=total_steps)
    tr = Trainer(model_config(c), optimizer(c), tcfg, make_plan(None))
    params = to_program(w)
    _same_layout(params, tr.params)
    tr.params = params
    jax.block_until_ready(tr.params)
    return tr


def train_one_step(tr, batches) -> dict:
    """One step through ``Trainer.train``, which returns after the step's
    metrics reached the host."""
    tr.tcfg.total_steps = tr.step + 1
    return tr.train(batches)[-1]


def serve_engine(c: dict, w: dict, *, slots: int, max_len: int):
    """A ``ServeEngine`` over the bench's weights ``w`` (greedy decoding)."""
    from repro.parallel.sharding import make_plan
    from repro.serve.engine import ServeConfig, ServeEngine

    s = c["system"]
    scfg = ServeConfig(
        slots=slots, max_len=max_len, paged=True, page_size=s["page_size"],
        prefix_cache=s["prefix_cache"], sample=False,
    )
    return ServeEngine(to_program(w), model_config(c), make_plan(None), scfg)


def request(rid: int, prompt, max_new: int):
    from repro.serve.batching import Request

    return Request(rid=rid, prompt=prompt, max_new_tokens=int(max_new))


def program_spans():
    """The program's own tracer (``repro.obs.trace``)."""
    from repro.obs import trace

    return trace


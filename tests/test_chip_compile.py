"""Compile the main-path Pallas kernels, and ``jax.grad`` through the
training-path ops, for a described TPU v5e at Mixtral-8x7B widths.

No chip is attached: the TPU compiler runs here against a described
``v5e:2x2`` topology and refuses what the chip would refuse (block shapes off
the (8, 128) tiling, kernels XLA cannot differentiate).  The topology is
described inside a fixture, never at import, so every pytest-xdist worker
collects the same tests and only the worker given this file loads libtpu.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_attention import (
    flash_attention_pallas,
    paged_flash_decode_pallas,
)
from repro.kernels.grouped_matmul import grouped_matmul_blocks_pallas, pick_tiles
from repro.kernels.moe_dispatch import moe_combine_pallas, moe_dispatch_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.topk_gating import topk_gating_pallas

# Mixtral-8x7B widths; the train smoke's tokens (4 x 1024), its 4 held
# experts, and the serve smoke's paged pool (4 slots x 8 pages of 16).
D, F, HQ, HKV, DH = 4096, 14336, 32, 8, 128
T, K, E = 4096, 2, 4
B, S = 4, 1024
SLOTS, PAGE, PAGES = 4, 16, 8
I32, F32, BF16 = jnp.int32, jnp.float32, jnp.bfloat16


def _gmm_blocks_specs(rows, experts, d, f):
    """The dropless GEMM's operands at the tile the rule picks for ``rows``
    routed rows (the static height ``dropless_plan`` gives)."""
    blk = pick_tiles(rows, experts, d, f)[0]
    n = (rows + experts * (blk - 1)) // blk
    return [((n, blk, d), BF16), ((experts, d, f), BF16), ((n,), I32), ((), I32)]


# The train step's w_in / w_gate (D -> F) and w_out (F -> D) calls, and the
# serving prefill's largest bucket (a 512-token prompt over all 8 experts).
GMM_SPECS = {
    "": _gmm_blocks_specs(T * K, E, D, F),
    "_down": _gmm_blocks_specs(T * K, E, F, D),
    "_prefill": _gmm_blocks_specs(512 * K, 8, D, F),
    "_prefill_down": _gmm_blocks_specs(512 * K, 8, F, D),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep these out of it.
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()


KERNELS = {
    "moe_dispatch": (moe_dispatch_pallas, [((T, D), BF16), ((T * K,), I32)]),
    "moe_combine": (
        moe_combine_pallas, [((T * K, D), BF16), ((T, K), I32), ((T, K), F32)]
    ),
    "topk_gating": (
        functools.partial(topk_gating_pallas, k=K), [((T, E), F32)]
    ),
    "flash_attention": (
        flash_attention_pallas,
        [((B, HQ, S, DH), BF16), ((B, HKV, S, DH), BF16), ((B, HKV, S, DH), BF16)],
    ),
    "rmsnorm": (rmsnorm_pallas, [((T, D), BF16), ((D,), BF16)]),
}
for _sfx, _specs in GMM_SPECS.items():
    KERNELS[f"grouped_matmul_blocks{_sfx}"] = (grouped_matmul_blocks_pallas, _specs)
for _c in (1, 4):
    KERNELS[f"paged_flash_decode_c{_c}"] = (
        paged_flash_decode_pallas,
        [
            ((SLOTS, _c, HQ, DH), BF16),
            ((SLOTS * PAGES, PAGE, HKV, DH), BF16),
            ((SLOTS * PAGES, PAGE, HKV, DH), BF16),
            ((SLOTS, PAGES), I32),
            ((SLOTS,), I32),
        ],
    )


def _shapes(specs, sharding):
    return [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in specs]


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, specs = KERNELS[name]
    compiled = jax.jit(fn).lower(*_shapes(specs, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# jax.grad through the custom_vjp ops: (op, differentiable args, all args).
GRADS = {
    "flash_attention": (
        ops.flash_attention, (0, 1, 2), KERNELS["flash_attention"][1]
    ),
    "grouped_matmul_blocks": (
        lambda x, w, be, used: ops.grouped_matmul(
            x, w, block_experts=be, used_blocks=used
        ),
        (0, 1),
        GMM_SPECS[""],
    ),
    "grouped_matmul_blocks_down": (
        lambda x, w, be, used: ops.grouped_matmul(
            x, w, block_experts=be, used_blocks=used
        ),
        (0, 1),
        GMM_SPECS["_down"],
    ),
    "grouped_matmul": (
        ops.grouped_matmul, (0, 1), [((E, 512, D), BF16), ((E, D, F), BF16)]
    ),
    "moe_dispatch": (ops.moe_dispatch, (0,), KERNELS["moe_dispatch"][1]),
    "moe_combine": (ops.moe_combine, (0, 2), KERNELS["moe_combine"][1]),
    "topk_gating": (
        lambda logits: ops.topk_gating(logits, K)[0], (0,), [((T, E), F32)]
    ),
}


@pytest.mark.parametrize("name", sorted(GRADS))
def test_grad_compiles_for_v5e(name, one_chip, monkeypatch):
    # Steer ops onto its TPU branch (the kernel, not interpret mode); the
    # process itself only sees CPU devices.
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    fn, argnums, specs = GRADS[name]

    def loss(*args):
        return jnp.sum(fn(*args).astype(F32))

    # value_and_grad keeps the forward kernel live (grad alone needs only
    # the primals, so XLA would drop it).
    step = jax.jit(jax.value_and_grad(loss, argnums=argnums))
    compiled = step.lower(*_shapes(specs, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()

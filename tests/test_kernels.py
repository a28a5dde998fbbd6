"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp oracle,
across shapes and dtypes."""

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import (
    flash_attention_pallas,
    paged_flash_decode_pallas,
)
from repro.kernels.grouped_matmul import grouped_matmul_pallas, pick_block
from repro.kernels.topk_gating import topk_gating_pallas

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize(
    "e,c,d,f",
    [(1, 8, 16, 16), (4, 64, 128, 256), (2, 32, 96, 64), (8, 128, 512, 384), (3, 16, 48, 80)],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul(e, c, d, f, dtype):
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (e, c, d), dtype)
    w = jax.random.normal(k2, (e, d, f), dtype)
    out = grouped_matmul_pallas(x, w, interpret=True)
    expect = ref.grouped_matmul(x, w)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    assert out.shape == (e, c, f) and out.dtype == dtype
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - expect.astype(jnp.float32)))) < tol * max(d, 1)


@pytest.mark.parametrize("t,e,k", [(64, 8, 2), (256, 64, 6), (128, 160, 6), (96, 16, 4), (32, 4, 1)])
def test_topk_gating(t, e, k):
    logits = jax.random.normal(KEY, (t, e), jnp.float32) * 2.0
    w, i = topk_gating_pallas(logits, k, interpret=True)
    rw, ri = ref.topk_gating(logits, k)
    assert float(jnp.max(jnp.abs(w - rw))) < 1e-5
    assert bool(jnp.all(i == ri))
    # weights sorted descending, valid expert range
    assert bool(jnp.all(w[:, :-1] >= w[:, 1:] - 1e-6))
    assert bool(jnp.all((i >= 0) & (i < e)))


@pytest.mark.parametrize(
    "case",
    [
        dict(b=2, hq=4, hkv=2, s=128, d=32, causal=True, window=None, softcap=None),
        dict(b=1, hq=8, hkv=8, s=256, d=64, causal=True, window=64, softcap=None),
        dict(b=1, hq=4, hkv=1, s=128, d=64, causal=True, window=None, softcap=50.0),
        dict(b=1, hq=2, hkv=2, s=192, d=64, causal=False, window=None, softcap=None),
        dict(b=2, hq=6, hkv=2, s=64, d=16, causal=True, window=16, softcap=20.0),
    ],
)
def test_flash_attention(case):
    c = dict(case)
    kq, kk, kv = jax.random.split(KEY, 3)
    q = jax.random.normal(kq, (c["b"], c["hq"], c["s"], c["d"]), jnp.float32)
    k = jax.random.normal(kk, (c["b"], c["hkv"], c["s"], c["d"]), jnp.float32)
    v = jax.random.normal(kv, (c["b"], c["hkv"], c["s"], c["d"]), jnp.float32)
    kw = dict(causal=c["causal"], window=c["window"], softcap=c["softcap"])
    out = flash_attention_pallas(q, k, v, bq=64, bk=64, interpret=True, **kw)
    expect = ref.flash_attention(q, k, v, **kw)
    assert float(jnp.max(jnp.abs(out - expect))) < 2e-5


@pytest.mark.parametrize(
    "case",
    [
        # C=1 decode and C>1 chunked continuations, GQA and MQA, page sizes
        # that do and don't divide the context, window and softcap on/off.
        dict(b=2, c=1, hq=4, hkv=2, d=32, page=16, p=4, window=None, softcap=None),
        dict(b=1, c=4, hq=4, hkv=1, d=64, page=8, p=6, window=None, softcap=30.0),
        dict(b=3, c=1, hq=2, hkv=2, d=16, page=16, p=2, window=16, softcap=None),
        dict(b=2, c=5, hq=6, hkv=3, d=32, page=8, p=8, window=24, softcap=25.0),
    ],
)
def test_paged_flash_decode_bit_exact_vs_oracle(case):
    """The acceptance gate for the paged decode kernel: interpret-mode pallas
    output is BIT-identical to the kernels/ref.py oracle (same streaming
    schedule), and allclose to a dense masked softmax over the gathered view."""
    c = dict(case)
    b, ch, hq, hkv, d, page, p = (
        c["b"], c["c"], c["hq"], c["hkv"], c["d"], c["page"], c["p"]
    )
    n_pool = b * p + 3
    kq, kk, kv, kt = jax.random.split(KEY, 4)
    q = jax.random.normal(kq, (b, ch, hq, d), jnp.float32)
    k_pool = jax.random.normal(kk, (n_pool, page, hkv, d), jnp.float32)
    v_pool = jax.random.normal(kv, (n_pool, page, hkv, d), jnp.float32)
    # Non-contiguous page ids; every sequence owns p distinct pool pages but
    # entries past its used span are -1 (unallocated).
    perm = jax.random.permutation(kt, n_pool)[: b * p].reshape(b, p)
    lengths = jnp.asarray(
        [(p * page - ch) - (i * page) // 2 for i in range(b)], jnp.int32
    )
    used = -(-(lengths + ch) // page)  # pages actually mapped
    table = jnp.where(jnp.arange(p)[None, :] < used[:, None], perm, -1)
    kw = dict(window=c["window"], softcap=c["softcap"])

    out = paged_flash_decode_pallas(
        q, k_pool, v_pool, table, lengths, interpret=True, **kw
    )
    oracle = ref.paged_flash_decode(q, k_pool, v_pool, table, lengths, **kw)
    assert out.shape == (b, ch, hq, d)
    assert float(jnp.max(jnp.abs(out - oracle))) == 0.0, c

    # dense reference: full softmax over the contiguous gathered view
    ck = ref.paged_gather_kv(k_pool, table)  # [B, P*page, Hkv, D]
    cv = ref.paged_gather_kv(v_pool, table)
    qg = q.reshape(b, ch, hkv, hq // hkv, d)
    s = jnp.einsum("bckgd,bskd->bkgcs", qg * (d**-0.5), ck)
    if c["softcap"]:
        s = jnp.tanh(s / c["softcap"]) * c["softcap"]
    q_pos = lengths[:, None, None, None, None] + jnp.arange(ch)[None, None, None, :, None]
    k_pos = jnp.arange(p * page)[None, None, None, None, :]
    mask = k_pos <= q_pos
    if c["window"]:
        mask &= k_pos > q_pos - c["window"]
    s = jnp.where(mask, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    expect = jnp.einsum("bkgcs,bskd->bckgd", w, cv).reshape(b, ch, hq, d)
    assert float(jnp.max(jnp.abs(out - expect))) < 1e-5, c


@pytest.mark.parametrize("c", [1, 2, 4])
def test_paged_flash_decode_verify_spans(c):
    """Speculative verify reads the pool at span widths C in {1, 2, 4}
    (serial decode, K=1 and K=3 draft/verify): interpret-mode pallas must be
    BIT-identical to the kernels/ref.py oracle on a permuted, non-contiguous
    page table — the verify pass re-scores drafted positions in place, so
    even ULP-level drift would break bit-exact acceptance."""
    b, hq, hkv, d, page, p = 3, 4, 2, 32, 8, 6
    n_pool = b * p + 5
    kq, kk, kv, kt = jax.random.split(jax.random.PRNGKey(c), 4)
    q = jax.random.normal(kq, (b, c, hq, d), jnp.float32)
    k_pool = jax.random.normal(kk, (n_pool, page, hkv, d), jnp.float32)
    v_pool = jax.random.normal(kv, (n_pool, page, hkv, d), jnp.float32)
    perm = jax.random.permutation(kt, n_pool)[: b * p].reshape(b, p)
    lengths = jnp.asarray([p * page - c - 1 - 3 * i for i in range(b)],
                          jnp.int32)
    used = -(-(lengths + c) // page)
    table = jnp.where(jnp.arange(p)[None, :] < used[:, None], perm, -1)
    out = paged_flash_decode_pallas(
        q, k_pool, v_pool, table, lengths, interpret=True
    )
    oracle = ref.paged_flash_decode(q, k_pool, v_pool, table, lengths)
    assert out.shape == (b, c, hq, d)
    assert float(jnp.max(jnp.abs(out - oracle))) == 0.0, c


def test_flash_attention_chunked_matches_ref():
    kq, kk, kv = jax.random.split(KEY, 3)
    q = jax.random.normal(kq, (2, 4, 256, 32))
    k = jax.random.normal(kk, (2, 2, 256, 32))
    v = jax.random.normal(kv, (2, 2, 256, 32))
    for kw in [dict(causal=True), dict(causal=True, window=64),
               dict(causal=True, softcap=30.0), dict(causal=False)]:
        a = ref.flash_attention(q, k, v, **kw)
        b = ref.flash_attention_chunked(q, k, v, bq=64, **kw)
        assert float(jnp.max(jnp.abs(a - b))) < 3e-6, kw


@pytest.mark.parametrize("n,b,d,f,e", [(4, 8, 32, 48, 3), (6, 16, 64, 128, 4), (2, 8, 16, 16, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_blocks(n, b, d, f, e, dtype):
    """Block-wise (dropless MegaBlocks layout) grouped GEMM: Pallas
    scalar-prefetch kernel vs the scan oracle vs a direct gather matmul."""
    from repro.kernels.grouped_matmul import grouped_matmul_blocks_pallas

    k1, k2, k3 = jax.random.split(KEY, 3)
    x = jax.random.normal(k1, (n, b, d), dtype)
    w = jax.random.normal(k2, (e, d, f), dtype)
    be = jax.random.randint(k3, (n,), 0, e)
    out = grouped_matmul_blocks_pallas(x, w, be, interpret=True)
    expect = ref.grouped_matmul_blocks(x, w, be)
    direct = jnp.einsum(
        "nbd,ndf->nbf", x.astype(jnp.float32), w.astype(jnp.float32)[be]
    )
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    assert out.shape == (n, b, f) and out.dtype == dtype
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - expect.astype(jnp.float32)))) < tol * d
    assert float(jnp.max(jnp.abs(expect.astype(jnp.float32) - direct))) < tol * d


@pytest.mark.parametrize("case", ["partial", "empty_expert", "one_expert", "none_used"])
def test_grouped_matmul_blocks_skips_unused_tiles(case):
    """Tiles past ``dropless_plan``'s used count: the kernel skips them and
    stores exact zeros, even where their rows hold data; the used tiles
    match the oracle.  Two f- and two d-steps, so a skipped tile's grid
    steps walk both axes."""
    from repro.kernels.grouped_matmul import grouped_matmul_blocks_pallas
    from repro.models import routing

    e, b, d, f = 3, 8, 256, 256
    k1, k2, k3 = jax.random.split(KEY, 3)
    dest = jax.random.randint(k1, (40,), 0, e)
    valid = None
    if case == "empty_expert":
        dest = jnp.where(dest == 1, 2, dest)
    elif case == "one_expert":
        dest = jnp.ones_like(dest)
    elif case == "none_used":
        valid = jnp.zeros(dest.shape, bool)
    rank, counts = routing.bucket_ranks(dest, e, valid=valid)
    plan = routing.dropless_plan(dest, rank, counts, valid, e, b)
    used = int(plan.used_blocks)
    n = plan.num_rows // b
    assert used == int(jnp.sum(-(-counts // b))) and used < n
    x = jax.random.normal(k2, (n, b, d))  # data in every tile, used or not
    w = jax.random.normal(k3, (e, d, f))
    out = grouped_matmul_blocks_pallas(
        x, w, plan.block_experts, plan.used_blocks, bf=128, bd=128, interpret=True
    )
    expect = ref.grouped_matmul_blocks(x, w, plan.block_experts, plan.used_blocks)
    assert float(jnp.max(jnp.abs(out - expect))) < 1e-4 * d
    assert float(jnp.max(jnp.abs(out[used:]))) == 0.0
    if used:
        assert float(jnp.min(jnp.max(jnp.abs(out[:used]), axis=(1, 2)))) > 0.0


@pytest.mark.parametrize(
    "rows,experts,d,f,want_b",
    [
        (8192, 4, 4096, 14336, 256),  # train step, w_in / w_gate
        (8192, 4, 14336, 4096, 256),  # train step, w_out
        (128, 8, 4096, 14336, 32),  # serving prefill, 64-token prompt
        (384, 8, 4096, 14336, 64),  # 192-token prompt
        (1024, 8, 4096, 14336, 256),  # 512-token prompt
        (64, 4, 32, 48, 32),  # CPU tests' tiny layer
        (16, 4, 32, 48, 8),
        (3, 8, 16, 16, 8),
    ],
)
def test_pick_tiles(rows, experts, d, f, want_b):
    """The dropless tile rule: B from the rows per expert, within 8-256;
    bf and bd from the widths alone, each dividing its width."""
    from repro.kernels.grouped_matmul import pick_tiles

    b, bf, bd = pick_tiles(rows, experts, d, f)
    assert b == want_b and b >= 8
    assert f % bf == 0 and d % bd == 0
    if d >= 1024 and f >= 1024:  # Mixtral widths: 1 MB weight tiles
        assert (bf, bd) == (512, 1024)
    for r in (1, rows, 7 * rows, 1 << 20):
        assert pick_tiles(r, experts, d, f)[1:] == (bf, bd)


@pytest.mark.parametrize("t,d,p", [(16, 32, 24), (64, 128, 64), (8, 48, 40)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_dispatch_kernel(t, d, p, dtype):
    """Scalar-prefetch gather kernel vs the jnp oracle, with empty slots."""
    from repro.kernels.moe_dispatch import moe_dispatch_pallas

    x = jax.random.normal(KEY, (t, d), dtype)
    src = jax.random.randint(jax.random.PRNGKey(1), (p,), -1, t)
    out = moe_dispatch_pallas(x, src, interpret=True)
    expect = ref.moe_dispatch(x, src)
    assert out.shape == (p, d) and out.dtype == dtype
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - expect.astype(jnp.float32)))) == 0.0
    # empty slots are zeroed
    assert float(jnp.max(jnp.abs(out[src < 0].astype(jnp.float32)))) == 0.0


@pytest.mark.parametrize("t,s,d,p", [(16, 2, 32, 40), (32, 4, 64, 96), (8, 1, 16, 8)])
def test_moe_combine_kernel(t, s, d, p):
    """Weighted combine kernel vs the jnp oracle, with dropped choices."""
    from repro.kernels.moe_dispatch import moe_combine_pallas

    y = jax.random.normal(KEY, (p, d))
    slot = jax.random.randint(jax.random.PRNGKey(1), (t, s), -1, p)
    w = jax.random.uniform(jax.random.PRNGKey(2), (t, s))
    out = moe_combine_pallas(y, slot, w, interpret=True)
    expect = ref.moe_combine(y, slot, w)
    assert out.shape == (t, d) and out.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(out - expect))) < 1e-5
    # a token whose every choice is dropped combines to exactly zero
    all_dropped = jnp.all(slot < 0, axis=1)
    assert float(jnp.max(jnp.abs(jnp.where(all_dropped[:, None], out, 0.0)))) == 0.0


def test_dispatch_combine_roundtrip():
    """dispatch -> combine with unit weights reconstructs kept token rows."""
    from repro.models import routing

    t, d, buckets, k = 24, 16, 4, 2
    x = jax.random.normal(KEY, (t, d))
    dest = jax.random.randint(jax.random.PRNGKey(3), (t * k,), 0, buckets)
    rank, counts = routing.bucket_ranks(dest, buckets)
    plan = routing.dropless_plan(dest, rank, counts, None, buckets, 8)
    src_tok = jnp.where(plan.src >= 0, plan.src // k, -1)
    packed = ref.moe_dispatch(x, src_tok)
    back = ref.moe_combine(
        packed, plan.slot.reshape(t, k), jnp.ones((t, k)) / k
    )
    assert float(jnp.max(jnp.abs(back - x))) < 1e-6


def _grad_case(name):
    """(op(*args, backend=...), differentiable args, all args) at small
    shapes, f32 so the only difference left is accumulation order."""
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    n = lambda i, shape: jax.random.normal(ks[i], shape, jnp.float32)
    if name == "flash_attention":
        return (lambda q, k, v, backend: ops.flash_attention(q, k, v, backend=backend),
                (0, 1, 2), (n(0, (1, 4, 64, 16)), n(1, (1, 2, 64, 16)), n(2, (1, 2, 64, 16))))
    if name == "grouped_matmul_blocks":
        be = jnp.asarray([0, 0, 1, 2, 2, 1], jnp.int32)
        return (lambda x, w, backend: ops.grouped_matmul(
                    x, w, block_experts=be, backend=backend),
                (0, 1), (n(0, (6, 8, 32)), n(1, (3, 32, 48))))
    if name == "grouped_matmul_blocks_padded":
        # dropless_plan's layout: expert 1 owns no tile, the last two tiles
        # are padding past the used count (their rows hold data here).
        be = jnp.asarray([0, 0, 2, 2, 2, 2], jnp.int32)
        return (lambda x, w, backend: ops.grouped_matmul(
                    x, w, block_experts=be, used_blocks=jnp.int32(4),
                    backend=backend),
                (0, 1), (n(0, (6, 8, 32)), n(1, (3, 32, 48))))
    if name == "grouped_matmul":
        return (lambda x, w, backend: ops.grouped_matmul(x, w, backend=backend),
                (0, 1), (n(0, (3, 16, 32)), n(1, (3, 32, 48))))
    if name == "moe_dispatch":
        src = jnp.asarray([3, -1, 0, 9, 9, 2, -1, 5], jnp.int32)
        return (lambda x, backend: ops.moe_dispatch(x, src, backend=backend),
                (0,), (n(0, (10, 32)),))
    if name == "moe_combine":
        slot = jnp.asarray([[0, 3], [-1, 7], [2, 2], [5, 1]], jnp.int32)
        return (lambda y, w, backend: ops.moe_combine(y, slot, w, backend=backend),
                (0, 1), (n(0, (8, 32)), jax.random.uniform(ks[1], (4, 2))))
    assert name == "topk_gating"
    return (lambda l, backend: ops.topk_gating(l, 2, backend=backend)[0],
            (0,), (n(0, (16, 8)) * 2.0,))


@pytest.mark.parametrize(
    "name",
    ["flash_attention", "grouped_matmul_blocks", "grouped_matmul",
     "moe_dispatch", "moe_combine", "topk_gating",
     "grouped_matmul_blocks_padded"],
)
def test_custom_vjp_grad_matches_oracle(name):
    """jax.grad through the Pallas op's custom_vjp (interpret mode) equals
    jax.grad of the ref.py oracle.  The backward IS the oracle's VJP except
    for the dropless GEMM's hand-written one, whose f32 sums run in another
    order: rtol 1e-5 of the largest gradient entry covers it."""
    fn, argnums, args = _grad_case(name)
    r = jax.random.normal(jax.random.PRNGKey(7), jax.eval_shape(
        lambda *a: fn(*a, backend="ref"), *args).shape)

    def grads(backend):
        return jax.grad(
            lambda *a: jnp.sum(fn(*a, backend=backend) * r), argnums=argnums
        )(*args)

    for got, want in zip(grads("pallas"), grads("ref")):
        assert got.shape == want.shape
        assert bool(jnp.all(jnp.isfinite(got)))
        scale = float(jnp.max(jnp.abs(want)))
        assert scale > 0.0
        assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * scale, name


def test_pick_block():
    assert pick_block(256, 128) == 128
    assert pick_block(96, 128) == 96
    assert pick_block(100, 64) == 50
    assert pick_block(7, 4) == 1


@pytest.mark.parametrize("t,d", [(64, 128), (256, 512), (96, 48)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_kernel(t, d, dtype):
    from repro.kernels.rmsnorm import rmsnorm_pallas
    from repro.models.layers import rms_norm

    x = jax.random.normal(KEY, (t, d), dtype) * 3
    w = jax.random.normal(KEY, (d,), dtype) * 0.1
    out = rmsnorm_pallas(x, w, interpret=True)
    expect = rms_norm(x[None], w)[0]
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert out.dtype == dtype
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - expect.astype(jnp.float32)))) < tol


@pytest.mark.parametrize("l,p,n", [(16, 16, 8), (64, 32, 16), (32, 64, 32)])
def test_ssd_chunk_kernel(l, p, n):
    """Pallas SSD chunk vs a direct O(L^2) reference."""
    from repro.kernels.ssd_chunk import ssd_chunk_pallas
    import numpy as np

    g = 3
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    x = jax.random.normal(k1, (g, l, p)) * 0.5
    da = -jnp.abs(jax.random.normal(k2, (g, l))) * 0.1
    bm = jax.random.normal(k3, (g, l, n)) * 0.5
    cm = jax.random.normal(k4, (g, l, n)) * 0.5
    y, st = ssd_chunk_pallas(x, da, bm, cm, interpret=True)

    # reference
    cum = jnp.cumsum(da, axis=1)
    cb = jnp.einsum("gln,gsn->gls", cm, bm)
    gate = jnp.exp(cum[:, :, None] - cum[:, None, :])
    mask = np.tril(np.ones((l, l), bool))
    y_ref = jnp.einsum("gls,gls,gsp->glp", cb, jnp.where(mask, gate, 0.0), x)
    st_ref = jnp.einsum("gsn,gs,gsp->gnp", bm, jnp.exp(cum[:, -1:] - cum), x)
    assert float(jnp.max(jnp.abs(y - y_ref))) < 1e-4
    assert float(jnp.max(jnp.abs(st - st_ref))) < 1e-4

"""Hierarchical (delegation) collectives == flat collectives, on 8 forced
host devices in a subprocess (this process keeps 1 device).

The first two tests drive the functional lowerings
(``hierarchical_all_to_all`` / ``hierarchical_psum`` / ``ring_all_gather``);
the rest drive the CommRuntime spec/op API directly: group-size x wire-perm
parity sweeps, the fused payload+metadata a2a (bit-identical to the unfused
pair), and the AllGather ring lowering across axis sizes including P=1.
The historical ``repro.core.collectives`` shim is gone — a guard test keeps
it from coming back as an import target."""

import pytest

HIER_A2A = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.commruntime import hierarchical_all_to_all, flat_all_to_all, hierarchical_psum

from repro.launch.mesh import make_mesh as _compat_make_mesh
mesh = _compat_make_mesh((8,), ('model',))
x = jnp.arange(8 * 8 * 4, dtype=jnp.float32).reshape(8, 8 * 4)  # per-dev [8,4]

def run(group):
    def hier(v):
        return hierarchical_all_to_all(v.reshape(8, 4), 'model', group).reshape(1, 32)
    def flat(v):
        return flat_all_to_all(v.reshape(8, 4), 'model').reshape(1, 32)
    h = jax.shard_map(hier, mesh=mesh, in_specs=P('model'), out_specs=P('model'))(x)
    f = jax.shard_map(flat, mesh=mesh, in_specs=P('model'), out_specs=P('model'))(x)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(f)), group

for g in (1, 2, 4, 8):
    run(g)

# hierarchical psum == plain psum over both axes
from repro.launch.mesh import make_mesh as _compat_make_mesh
mesh2 = _compat_make_mesh((2, 4), ('data', 'model'))
y = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)
a = jax.shard_map(lambda v: hierarchical_psum(v, 'model', 'data', scatter_dim=0),
                  mesh=mesh2, in_specs=P(('data', 'model')), out_specs=P(('data', 'model')))(y)
b = jax.shard_map(lambda v: jax.lax.psum(jax.lax.psum(v, 'model'), 'data'),
                  mesh=mesh2, in_specs=P(('data', 'model')), out_specs=P(('data', 'model')))(y)
np.testing.assert_allclose(np.asarray(a), np.asarray(b))
print('COLLECTIVES_OK')
"""


def test_hierarchical_collectives_multidevice(multidevice):
    out = multidevice(HIER_A2A, devices=8)
    assert "COLLECTIVES_OK" in out


RING_AG = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.commruntime import ring_all_gather
from repro.launch.mesh import make_mesh as _compat_make_mesh
mesh = _compat_make_mesh((8,), ('model',))
x = jnp.arange(8 * 2 * 3, dtype=jnp.float32).reshape(8 * 2, 3)
ring = jax.shard_map(lambda v: ring_all_gather(v, 'model'), mesh=mesh,
                     in_specs=P('model'), out_specs=P(None), check_vma=False)(x)
ref = jax.shard_map(lambda v: jax.lax.all_gather(v, 'model', axis=0, tiled=True),
                    mesh=mesh, in_specs=P('model'), out_specs=P(None), check_vma=False)(x)
np.testing.assert_allclose(np.asarray(ring), np.asarray(ref))
print('RING_OK')
"""


def test_ring_all_gather(multidevice):
    out = multidevice(RING_AG, devices=8)
    assert "RING_OK" in out


PARITY_SWEEP = """
import itertools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.commruntime import AllToAll, CommSpec
from repro.launch.mesh import make_mesh as _compat_make_mesh

PDEV = 8
mesh = _compat_make_mesh((PDEV,), ('model',))
x = jax.random.normal(jax.random.PRNGKey(0), (PDEV * PDEV, 4))  # per-dev [P,4]
rng = np.random.default_rng(0)
perms = [None, tuple(rng.permutation(PDEV).tolist()), tuple(np.roll(np.arange(PDEV), 3).tolist())]

def run(spec):
    op = AllToAll(spec)
    f = jax.shard_map(lambda v: op(v.reshape(PDEV, 4)).reshape(1, PDEV * 4),
                          mesh=mesh, in_specs=P('model'), out_specs=P('model'))
    return np.asarray(f(x))

# Sweep group sizes {1, 2, P/2, P} x non-identity dest/src perms: every
# hierarchical factorization must be BIT-identical to the flat lowering
# under the same wire re-addressing.
for dp, sp in itertools.product(perms, perms):
    flat = run(CommSpec(axis='model', axis_size=PDEV, group_size=1,
                        dest_perm=dp, src_perm=sp))
    for g in (2, PDEV // 2, PDEV):
        hier = run(CommSpec(axis='model', axis_size=PDEV, group_size=g,
                            dest_perm=dp, src_perm=sp))
        np.testing.assert_array_equal(hier, flat), (g, dp, sp)
    # a non-identity dest_perm must actually move chunks
    if dp is not None and list(dp) != list(range(PDEV)):
        ident = run(CommSpec(axis='model', axis_size=PDEV, group_size=1, src_perm=sp))
        assert not np.array_equal(flat, ident), (dp, sp)
    # the reconfigure hook reproduces the statically-built spec
    hooked = run(AllToAll(CommSpec(axis='model', axis_size=PDEV, group_size=2))
                 .reconfigure(dest_perm=dp, src_perm=sp).spec)
    np.testing.assert_array_equal(hooked, flat)

# Permute shares AllToAll's GATHER semantics: after the hop, device k holds
# the payload of device dest_perm[k] (one dest_perm = one routing family-wide).
from repro.core.commruntime import Permute
blocks = jnp.arange(PDEV, dtype=jnp.float32).reshape(PDEV, 1)  # device k holds [k]
p = tuple(rng.permutation(PDEV).tolist())
op = Permute(CommSpec(axis='model', axis_size=PDEV)).reconfigure(dest_perm=p)
moved = jax.shard_map(lambda v: op(v), mesh=mesh,
                          in_specs=P('model'), out_specs=P('model'),
                          check_vma=False)(blocks)
np.testing.assert_array_equal(np.asarray(moved)[:, 0], np.asarray(p, np.float32))
# default: +1 ring shift of the blocks (device k receives from k-1)
ring = jax.shard_map(lambda v: Permute(CommSpec(axis='model', axis_size=PDEV))(v),
                         mesh=mesh, in_specs=P('model'), out_specs=P('model'),
                         check_vma=False)(blocks)
np.testing.assert_array_equal(np.asarray(ring)[:, 0],
                              np.roll(np.arange(PDEV, dtype=np.float32), 1))
print('PARITY_SWEEP_OK')
"""


def test_hierarchical_parity_under_wire_perms(multidevice):
    """Satellite: group sizes {1, 2, P/2, P} x non-identity dest/src perms."""
    out = multidevice(PARITY_SWEEP, devices=8, timeout=900)
    assert "PARITY_SWEEP_OK" in out


FUSED_A2A = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.commruntime import AllToAll, CommSpec
from repro.launch.mesh import make_mesh as _compat_make_mesh

PDEV, C, D = 8, 6, 10
mesh = _compat_make_mesh((PDEV,), ('model',))

for dtype in (jnp.float32, jnp.bfloat16):
    x = jax.random.normal(jax.random.PRNGKey(0), (PDEV * PDEV, C, D)).astype(dtype)
    e = jax.random.randint(jax.random.PRNGKey(1), (PDEV * PDEV, C), -1, 7).astype(jnp.int32)
    for g in (1, 2, 4):
        op = AllToAll(CommSpec(axis='model', axis_size=PDEV, group_size=g))
        def fused(v, m):
            rx, re = op.fused(v, m)
            return rx, re
        def unfused(v, m):
            return op(v), op(m[..., None])[..., 0]
        sm = lambda f: jax.shard_map(f, mesh=mesh, in_specs=(P('model'), P('model')),
                                         out_specs=(P('model'), P('model')), check_vma=False)
        fx, fe = sm(fused)(x, e)
        ux, ue = sm(unfused)(x, e)
        # ONE packed wire transfer == the unfused pair, BIT-identical
        np.testing.assert_array_equal(np.asarray(fx).view(np.uint8),
                                      np.asarray(ux).view(np.uint8)), (dtype, g)
        np.testing.assert_array_equal(np.asarray(fe), np.asarray(ue)), (dtype, g)

    # gradients flow through the fused payload identically (metadata lanes carry none)
    x32 = jax.random.normal(jax.random.PRNGKey(2), (PDEV * PDEV, C, D))
    op = AllToAll(CommSpec(axis='model', axis_size=PDEV, group_size=2))
    def loss_fused(v, m):
        rx, _ = op.fused(v, m)
        return (rx ** 2).sum()
    def loss_unfused(v, m):
        return (op(v) ** 2).sum()
    smg = lambda f: jax.shard_map(
        lambda v, m: jax.grad(f)(v, m), mesh=mesh,
        in_specs=(P('model'), P('model')), out_specs=P('model'), check_vma=False)
    gf = smg(loss_fused)(x32, e)
    gu = smg(loss_unfused)(x32, e)
    np.testing.assert_array_equal(np.asarray(gf), np.asarray(gu))
print('FUSED_A2A_OK')
"""


def test_fused_payload_metadata_a2a_bit_identical(multidevice):
    """Satellite: the packed payload+gate transfer == the unfused pair."""
    out = multidevice(FUSED_A2A, devices=8, timeout=900)
    assert "FUSED_A2A_OK" in out


RING_OP_SIZES = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.commruntime import AllGather, CommSpec
from repro.launch.mesh import make_mesh as _compat_make_mesh

# Equivalence of the runtime's ring AllGather lowering vs lax.all_gather
# across axis sizes, INCLUDING the P=1 degenerate mesh.
for p in (1, 2, 4, 8):
    mesh = _compat_make_mesh((p,), ('model',))
    x = jnp.arange(p * 2 * 3, dtype=jnp.float32).reshape(p * 2, 3)
    ring_op = AllGather(CommSpec(axis='model', axis_size=p), impl='ring')
    flat_op = AllGather(CommSpec(axis='model', axis_size=p), impl='flat')
    run = lambda op: jax.shard_map(lambda v: op(v), mesh=mesh,
                                       in_specs=P('model'), out_specs=P(None),
                                       check_vma=False)(x)
    ref = jax.shard_map(lambda v: jax.lax.all_gather(v, 'model', axis=0, tiled=True),
                            mesh=mesh, in_specs=P('model'), out_specs=P(None),
                            check_vma=False)(x)
    np.testing.assert_array_equal(np.asarray(run(ring_op)), np.asarray(ref)), p
    np.testing.assert_array_equal(np.asarray(run(flat_op)), np.asarray(ref)), p
print('RING_OP_OK')
"""


def test_allgather_ring_op_axis_sizes(multidevice):
    """Satellite: ring_all_gather wired as the AllGather ring lowering,
    equivalent to lax.all_gather for P in {1, 2, 4, 8}."""
    out = multidevice(RING_OP_SIZES, devices=8, timeout=900)
    assert "RING_OP_OK" in out


def test_allgather_op_single_device_no_mesh():
    """P=1 without any mesh at all: the op degrades to identity."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.commruntime import AllGather, CommSpec

    x = jnp.arange(6.0).reshape(2, 3)
    out = AllGather(CommSpec(), impl="ring")(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_collectives_shim_removed():
    """The deprecated ``repro.core.collectives`` shim is deleted: importing
    it fails, the package namespace no longer exposes it, and no in-repo
    module (src or tests) references it as an import target."""
    import importlib
    import os
    import re

    import pytest as _pytest

    import repro.core

    with _pytest.raises(ImportError):
        importlib.import_module("repro.core" + ".collectives")
    with _pytest.raises(AttributeError):
        repro.core.collectives  # noqa: B018
    assert "collectives" not in repro.core.__all__

    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(here)
    pat = re.compile(r"^\s*(from|import)\s+repro\.core\.collectives\b")
    offenders = []
    for top in (os.path.join(root, "src"), here):
        for dirpath, _, files in os.walk(top):
            for f in files:
                if not f.endswith(".py"):
                    continue
                path = os.path.join(dirpath, f)
                with open(path) as fh:
                    for line in fh:
                        if pat.match(line):
                            offenders.append(path)
    assert not offenders, offenders

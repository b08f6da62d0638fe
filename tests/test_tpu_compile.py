"""Every device program the serving path can select on a TPU, AOT-compiled
for a *described* v5e:2x2 (no chip attached) at the 1M-subscription shapes.

The TPU's compiler is installed beside JAX and compiles for a topology
that is only described; what it refuses here, the chip refuses too. Four
Pallas kernels passed every interpret-mode test and were refused by it
(PR 27) — these tests are what stands in their place: the plain-XLA
programs that serve on TPU must keep compiling and keep fitting one
chip's 16 GB, by ``memory_analysis()``.

Shapes (BASELINE config 2, 1,000,000 wildcard subscriptions, seed 0):
1,588,983 trie nodes padded by ``PatchableTrie`` to 2,097,152 arena rows,
524,288 edge buckets of ``probe_len`` 16, walk width 17 (16 levels).

The topology is described inside a module-scoped fixture and nowhere at
import time: only one process may load the TPU library, and every xdist
worker imports every test file.
"""

import numpy as np
import pytest

N_NODES = 2_097_152
N_BUCKETS = 524_288
PROBE_LEN = 16
WIDTH = 17
HBM_BYTES = 16 * 1024 ** 3
WALK_KW = dict(probe_len=PROBE_LEN, k_states=32, max_intervals=32, esc_k=0)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this image
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from jax.sharding import Mesh
    from bifromq_tpu.parallel.sharded import REPLICA_AXIS, SHARD_AXIS
    return Mesh(np.array(topo.devices).reshape(1, 4),
                (REPLICA_AXIS, SHARD_AXIS))


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip — keep these out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _trie(sh):
    import jax.numpy as jnp
    from bifromq_tpu.ops.match import DeviceTrie
    i32 = jnp.int32
    return DeviceTrie(
        node_tab=_spec((N_NODES, 12), i32, sh),
        edge_tab=_spec((N_BUCKETS, PROBE_LEN, 4), i32, sh),
        child_list=_spec((N_NODES,), i32, sh),
        count_tab=_spec((N_NODES, 4), i32, sh),
        route_tab=_spec((N_NODES, 8), i32, sh))


def _probes(b, sh):
    import jax.numpy as jnp
    from bifromq_tpu.ops.match import Probes
    i32 = jnp.int32
    return Probes(_spec((b, WIDTH), i32, sh), _spec((b, WIDTH), i32, sh),
                  _spec((b,), i32, sh), _spec((b,), i32, sh),
                  _spec((b,), jnp.bool_, sh))


def _fits(compiled, record_property, name):
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes)
    record_property(name, {"args": ma.argument_size_in_bytes,
                           "out": ma.output_size_in_bytes,
                           "temp": ma.temp_size_in_bytes,
                           "code": ma.generated_code_size_in_bytes})
    assert total < HBM_BYTES, (name, total)
    # nothing Mosaic-compiled is left on the serving path; a Pallas
    # kernel that comes back must assert the opposite here
    assert "tpu_custom_call" not in compiled.as_text()
    return ma


@pytest.mark.parametrize("batch", [256, 16_384])
@pytest.mark.parametrize("donated", [False, True], ids=["plain", "donated"])
def test_walk_routes(one_chip, no_compile_cache, record_property, batch,
                     donated):
    from bifromq_tpu.ops import match as M
    fn = M._walk_routes_donated_jit if donated else M.walk_routes
    compiled = fn.lower(_trie(one_chip), _probes(batch, one_chip),
                        **WALK_KW).compile()
    ma = _fits(compiled, record_property, f"walk B={batch}")
    # the walk reads route_tab + edge_tab only: the other tables must
    # not be program arguments (they would double the resident bytes)
    assert ma.argument_size_in_bytes < 300 * 1024 ** 2


def test_walk_routes_escalation_budget(one_chip, no_compile_cache,
                                       record_property):
    """The host-triggered escalation re-walk: 4x states, 4x intervals."""
    from bifromq_tpu.ops import match as M
    compiled = M.walk_routes.lower(
        _trie(one_chip), _probes(64, one_chip), probe_len=PROBE_LEN,
        k_states=128, max_intervals=128, esc_k=0).compile()
    _fits(compiled, record_property, "walk escalation")


@pytest.mark.parametrize("batch,n_peers", [(256, 0), (256, 2), (4_096, 2),
                                           (4_096, 64), (16_384, 0)])
def test_expand_routes(one_chip, no_compile_cache, record_property, batch,
                       n_peers):
    import jax.numpy as jnp
    from bifromq_tpu.ops import match as M
    i32 = jnp.int32
    compiled = M._expand_routes_fn.lower(
        _spec((batch, 32), i32, one_chip), _spec((batch, 32), i32, one_chip),
        _spec((batch,), jnp.bool_, one_chip),
        _spec((1_048_576,), i32, one_chip),
        cap=batch * 64, n_peers=n_peers).compile()
    _fits(compiled, record_property, f"expand B={batch} peers={n_peers}")


@pytest.mark.parametrize("table,shape", [
    ("node_tab", (N_NODES, 12)), ("count_tab", (N_NODES, 4)),
    ("route_tab", (N_NODES, 8)), ("edge_tab", (N_BUCKETS, PROBE_LEN, 4))])
@pytest.mark.parametrize("donated", [False, True], ids=["plain", "donated"])
def test_patch_scatter(one_chip, no_compile_cache, record_property, table,
                       shape, donated):
    import jax.numpy as jnp
    from bifromq_tpu.ops import match as M
    fn = M._scatter_rows_donated if donated else M._scatter_rows
    i32 = jnp.int32
    for rows in (M._PATCH_CHUNK,):      # the one shape a flush scatters
        compiled = fn.lower(_spec(shape, i32, one_chip),
                            _spec((rows,), i32, one_chip),
                            _spec((rows,) + shape[1:], i32, one_chip)
                            ).compile()
        _fits(compiled, record_property, f"scatter {table} rows={rows}")


@pytest.mark.parametrize("batch", [256, 16_384])
def test_device_tokenizer(one_chip, no_compile_cache, record_property,
                          batch):
    import jax.numpy as jnp
    from bifromq_tpu.ops import tokenize as T
    i32, u32 = jnp.int32, jnp.uint32
    compiled = T._hash_lanes_lax.lower(
        _spec((batch, T.tok_max_bytes()), jnp.uint8, one_chip),
        _spec((batch, WIDTH), i32, one_chip),
        _spec((batch, WIDTH), i32, one_chip),
        _spec((batch, 1), i32, one_chip),
        _spec((1, 8), u32, one_chip), _spec((1, 8), u32, one_chip)).compile()
    _fits(compiled, record_property, f"tokenize B={batch}")


@pytest.mark.parametrize("merge_total", [False, True],
                         ids=["walk_only", "psum_total"])
def test_mesh_match_step(mesh4, no_compile_cache, record_property,
                         merge_total):
    """The four-device mesh step with NamedShardings over the described
    devices: per-device bytes are a quarter of the tables."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from bifromq_tpu.parallel import sharded as S
    ts = NamedSharding(mesh4, P(S.SHARD_AXIS))
    ps = NamedSharding(mesh4, P(S.REPLICA_AXIS, S.SHARD_AXIS))
    i32 = jnp.int32
    b = 4_096
    step = S.make_match_step(mesh4, probe_len=PROBE_LEN, k_states=32,
                             merge_total=merge_total)
    compiled = step.lower(
        _spec((4, N_BUCKETS // 4, PROBE_LEN, 4), i32, ts),
        _spec((4, N_NODES // 4), i32, ts),
        _spec((4, N_NODES // 4, 8), i32, ts),
        _spec((1, 4, b, WIDTH), i32, ps), _spec((1, 4, b, WIDTH), i32, ps),
        _spec((1, 4, b), i32, ps), _spec((1, 4, b), i32, ps),
        _spec((1, 4, b), jnp.bool_, ps)).compile()
    ma = _fits(compiled, record_property, f"mesh step merge={merge_total}")
    assert ma.argument_size_in_bytes < 100 * 1024 ** 2     # per device
    assert ("all-reduce" in compiled.as_text()) == merge_total


@pytest.mark.parametrize("n_peers", [0, 2])
def test_mesh_expand_step_ring(mesh4, no_compile_cache, record_property,
                               n_peers):
    """The expand step's per-peer totals merge is a ring of neighbor
    permutes on the chip interconnect, not an all-reduce."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from bifromq_tpu.parallel import sharded as S
    ts = NamedSharding(mesh4, P(S.SHARD_AXIS))
    ps = NamedSharding(mesh4, P(S.REPLICA_AXIS, S.SHARD_AXIS))
    i32 = jnp.int32
    b = 4_096
    step = S.make_expand_step(mesh4, cap=b * 64, n_peers=n_peers)
    compiled = step.lower(
        _spec((1, 4, b, 32), i32, ps), _spec((1, 4, b, 32), i32, ps),
        _spec((1, 4, b), jnp.bool_, ps),
        _spec((4, 262_144), i32, ts)).compile()
    _fits(compiled, record_property, f"mesh expand peers={n_peers}")
    assert "collective-permute" in compiled.as_text()


def test_mesh_shard_scatter(mesh4, no_compile_cache, record_property):
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from bifromq_tpu.parallel import sharded as S
    ts = NamedSharding(mesh4, P(S.SHARD_AXIS))
    rep = NamedSharding(mesh4, P())
    i32 = jnp.int32
    for fn in (S._shard_scatter, S._shard_scatter_donated):
        compiled = fn.lower(_spec((4, N_NODES // 4, 8), i32, ts),
                            _spec((8,), i32, rep), _spec((8, 8), i32, rep),
                            shard=2).compile()
        _fits(compiled, record_property, "mesh shard scatter")


@pytest.mark.parametrize("n_buckets,n_nodes", [(1_048_576, 4_194_304),
                                               (2_097_152, 8_388_608)],
                         ids=["2.5M", "5M"])
def test_retained_walk_reads_resident_edge_table(one_chip, no_compile_cache,
                                                 record_property, n_buckets,
                                                 n_nodes):
    """The retained walk at the retained topics' shapes (2.5M and 5M
    Homie topics: the arenas ``RetainedIndex`` pads them to) gathers its
    edge buckets from the [NB, P, 4] table as it lies in HBM: no copy,
    reshape or transpose of a table-sized operand, and next to no temp.
    A [NB, P*4] view of the table re-lays it out inside the walk's loop
    (805,919,232 temp bytes at 2.5M)."""
    import re
    import jax.numpy as jnp
    from bifromq_tpu.models.automaton import EXT_COLS, NODE_COLS
    from bifromq_tpu.ops import retained as R
    i32 = jnp.int32
    b = 16
    tables = R.RetainedDeviceTables(
        node_tab=_spec((n_nodes, NODE_COLS), i32, one_chip),
        edge_tab=_spec((n_buckets, PROBE_LEN, 4), i32, one_chip),
        child_list=_spec((n_nodes,), i32, one_chip),
        ext_tab=_spec((n_nodes, EXT_COLS), i32, one_chip),
        extra_list=_spec((64,), i32, one_chip))
    probes = R.FilterProbes(
        _spec((b, WIDTH), i32, one_chip), _spec((b, WIDTH), i32, one_chip),
        _spec((b, WIDTH), i32, one_chip), _spec((b,), i32, one_chip),
        _spec((b,), i32, one_chip))
    compiled = R.retained_walk_ext.lower(tables, probes,
                                         k_states=32).compile()
    ma = _fits(compiled, record_property, f"retained walk NB={n_buckets}")
    relayouts = [line.strip() for line in compiled.as_text().splitlines()
                 if re.search(r"= \S+\[%d[,\]]\S* (copy|reshape|transpose)\("
                              % n_buckets, line)]
    assert not relayouts, relayouts[:4]
    assert ma.temp_size_in_bytes < 64 * 1024 ** 2, ma.temp_size_in_bytes

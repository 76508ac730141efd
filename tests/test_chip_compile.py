"""Compile rehearsals for the chip: the federated path's Pallas kernels at
the shapes of the cold start, compiled through Mosaic for a described TPU
v5e chip (``interpret=False``), and the pinned cohort gather at the
benchmark cells' stack shapes, on one chip and on the four-chip data
mesh. Nothing runs, so these say nothing about
results or times — they catch what the chip's compiler refuses (layouts,
tiling, VMEM), or a program that moves the whole pinned stack, before any
chip time is spent.

The topology is described in a fixture, never at import: only one process
at a time may load the TPU library, and every test worker imports every
test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.fed import parallel as parallel_lib
from repro.fed import rounds as rounds_lib
from repro.fed.engine import gather_cohort
from repro.kernels.edc_cosine import edc_cosine
from repro.kernels.madc import madc_block
from repro.models.paper_models import mclr
from repro.sharding.specs import cohort_pspec, data_axis_names

D_W_FEMNIST_MLP = 415_258      # paper Table 2, FEMNIST MLP-512

# the pinned train stacks (clients, padded rows, features) of the
# benchmark's two cells: mnist_mlp128.pinned, femnist_mlp512.pinned
PINNED_STACKS = [(1000, 410, 784), (200, 320, 784)]

# ops that hand a buffer on without moving it: a loop's carry holds the
# stack it reads
PASS_THROUGH = {"parameter", "get-tuple-element", "tuple", "while"}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                              # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.usefixtures("no_persistent_cache")
class TestKernelsCompileForV5e:
    @pytest.mark.parametrize("n", [60, 128, 200, 512])
    def test_madc_block(self, one_chip, n):
        M = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=one_chip)
        text = _compiled_text(lambda m: madc_block(m, interpret=False), M)
        assert "tpu_custom_call" in text

    def test_edc_cosine_at_femnist_mlp_width(self, one_chip):
        dW = jax.ShapeDtypeStruct((60, D_W_FEMNIST_MLP), jnp.float32,
                                  sharding=one_chip)
        V = jax.ShapeDtypeStruct((D_W_FEMNIST_MLP, 3), jnp.float32,
                                 sharding=one_chip)
        text = _compiled_text(lambda a, b: edc_cosine(a, b, interpret=False),
                              dW, V)
        assert "tpu_custom_call" in text


def _instructions(text: str) -> list:
    """(opcode, result type, computation) of every instruction in compiled
    HLO text."""
    out, comp = [], None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+) .*\{$", line.rstrip())
        if head and not line[:1].isspace():
            comp = head.group(1)
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%?[\w.\-]+ = (.*)$", line)
        if m:
            op = re.search(r"(?<![\w.])([a-z][a-z0-9\-]*)\(", m.group(1))
            out.append((op.group(1) if op else "",
                        m.group(1)[:op.start() if op else None], comp))
    return out


def _loop_computations(text: str) -> set:
    """Every computation a ``while`` of compiled HLO text runs each trip:
    its bodies and conditions, and all they call, transitively."""
    calls, comp = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+) .*\{$", line.rstrip())
        if head and not line[:1].isspace():
            comp = head.group(1)
            calls[comp] = set()
        elif comp is not None:
            calls[comp] |= set(re.findall(r"%([\w.\-]+)", line))
    todo = [c for line in text.splitlines() if " while(" in line
            for c in re.findall(r"(?:body|condition)=%?([\w.\-]+)", line)]
    seen = set()
    while todo:
        c = todo.pop()
        if c in calls and c not in seen:
            seen.add(c)
            todo.extend(calls[c])
    return seen


def _packed(shape):
    """The packed (x, y) shapes of an (N, S, D) train stack: each client's
    rows in whole (8, 128) tiles (``fed.rounds.pack_clients``)."""
    N, S, D = shape
    tiles = lambda r: (N, -(-r // 1024) * 8, 128)
    return tiles(S * D), tiles(S)


def _whole_stack_ops(text: str, shape) -> list:
    """Instructions other than pass-throughs whose result is a whole pinned
    stack: packed ``x`` or ``y`` of an (N, S, D) train stack."""
    return [(op, ty.strip()[:80], comp)
            for op, ty, comp in _instructions(text)
            if op not in PASS_THROUGH and _is_whole_stack(ty, shape)]


def _is_whole_stack(ty: str, shape) -> bool:
    return any(f"[{','.join(map(str, p))}]" in ty for p in _packed(shape))


def _pinned_stack(shape, sharding):
    """Abstract ``ClientStack`` of an (N, S, D) train stack, as the trainer
    pins it: packed, default layouts. ``sharding``: one for every leaf, or
    a mesh, whose data axes split each leaf's client axis as
    ``fed.parallel.shard_client_axis`` splits it."""
    N, S, D = shape
    px, py = _packed(shape)
    place = lambda nd: sharding
    if isinstance(sharding, Mesh):
        place = lambda nd: NamedSharding(sharding, cohort_pspec(
            nd, data_axes=data_axis_names(sharding)))
    return rounds_lib.ClientStack(
        x=jax.ShapeDtypeStruct(px, jnp.float32, sharding=place(3)),
        y=jax.ShapeDtypeStruct(py, jnp.int32, sharding=place(3)),
        n=jax.ShapeDtypeStruct((N,), jnp.int32, sharding=place(1)),
        x_rows=(S, D), y_rows=(S,))


@pytest.mark.usefixtures("no_persistent_cache")
class TestCohortGatherCompilesForV5e:
    """The cohort gather on a client-major stack is K block copies: no op
    of the program copies, slices, transposes or fuses the whole stack, and
    the program does not grow with K."""

    def _gather(self, one_chip, shape, k):
        sel = jax.ShapeDtypeStruct((k,), jnp.int32, sharding=one_chip)
        return gather_cohort.lower(_pinned_stack(shape, one_chip),
                                   sel).compile()

    def _gather_text(self, one_chip, shape, k):
        return self._gather(one_chip, shape, k).as_text()

    @pytest.mark.parametrize("shape", PINNED_STACKS,
                             ids=["mnist_stack", "femnist_stack"])
    def test_no_whole_stack_op(self, one_chip, shape):
        compiled = self._gather(one_chip, shape, 20)
        # the chip's default layout keeps the packed stack client-major
        packed = compiled.input_formats[0][0]
        assert packed.x.layout.major_to_minor == (0, 1, 2)
        assert packed.y.layout.major_to_minor == (0, 1, 2)
        text = compiled.as_text()
        assert "dynamic-slice" in text
        assert _whole_stack_ops(text, shape) == []

    @pytest.mark.parametrize("shape", PINNED_STACKS,
                             ids=["mnist_stack", "femnist_stack"])
    def test_program_does_not_grow_with_k(self, one_chip, shape):
        n20 = len(_instructions(self._gather_text(one_chip, shape, 20)))
        n200 = len(_instructions(self._gather_text(one_chip, shape, 200)))
        assert abs(n200 - n20) <= 4, (n20, n200)

    def test_block_executor_gather(self, one_chip):
        """The block executor's in-program gather, compiled in the whole
        block program (a small model over MNIST's pinned stack). The
        solver's dots take bf16 operands on a TPU, and XLA hoists that
        convert of the gathered rows out of the scan onto the whole stack:
        one pass a block dispatch, outside the loop, and the only
        whole-stack op left."""
        shape = PINNED_STACKS[0]
        N, S, D = shape
        block_fn = rounds_lib.make_block_executor(
            mclr(D, 10), epochs=1, batch_size=S, lr=0.1, mu=0.0,
            n_groups=2, max_samples=S)
        model = mclr(D, 10)
        group = jax.eval_shape(
            lambda: jax.tree_util.tree_map(
                lambda p: jnp.stack([p, p]),
                model.init(jax.random.PRNGKey(0))))
        glob = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        put = lambda t: jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), t)
        d_w = sum(a.size for a in jax.tree_util.tree_leaves(glob))
        carry = dict(group_params=put(group), global_params=put(glob),
                     group_delta=put(jax.ShapeDtypeStruct((2, d_w),
                                                          jnp.float32)),
                     membership=put(jax.ShapeDtypeStruct((N + 1,),
                                                         jnp.int32)),
                     aux=None)
        test = put((jax.ShapeDtypeStruct((N, 8, D), jnp.float32),
                    jax.ShapeDtypeStruct((N, 8), jnp.int32),
                    jax.ShapeDtypeStruct((N,), jnp.int32)))
        B, K = 2, 20
        staged = put((jax.ShapeDtypeStruct((B, K), jnp.int32),
                      jax.ShapeDtypeStruct((B, K, 2), jnp.uint32),
                      jax.ShapeDtypeStruct((B, K), jnp.float32),
                      jax.ShapeDtypeStruct((B,), jnp.bool_)))
        text = jax.jit(block_fn).lower(
            carry, _pinned_stack(shape, one_chip), test,
            *staged).compile().as_text()
        ops = _whole_stack_ops(text, shape)
        assert [o for o in ops if o[0] != "convert"] == []
        assert len(ops) <= 1, ops
        in_loop = _loop_computations(text)
        assert in_loop                         # the scan is a while
        assert [o for o in ops if o[2] in in_loop] == []


@pytest.fixture(scope="module")
def data_mesh(topo):
    """The described 2x2 v5e as a four-slice data mesh."""
    return Mesh(np.array(topo.devices).reshape(-1), ("data",))


@pytest.mark.usefixtures("no_persistent_cache")
class TestShardedGatherCompilesForV5e:
    """On a mesh that spreads the stack's client axis over its four chips,
    a slice along that axis all-gathers the whole stack. There the gather
    takes the branch ``fed.parallel.shards_client_axis`` picks: the index
    gather, which moves only the cohort."""

    def _gather(self, data_mesh, shape, sharded):
        # one round's cohort ids, split over the data axes as the block
        # executor splits its staged cohorts
        sel = jax.ShapeDtypeStruct((20,), jnp.int32, sharding=NamedSharding(
            data_mesh, cohort_pspec(1, data_axes=("data",))))
        fn = lambda st, ix: rounds_lib.gather_clients(st, ix,
                                                      sharded=sharded)
        return _instructions(jax.jit(fn).lower(
            _pinned_stack(shape, data_mesh), sel).compile().as_text())

    @pytest.mark.parametrize("shape", PINNED_STACKS,
                             ids=["mnist_stack", "femnist_stack"])
    def test_rule_picks_a_gather_without_all_gather(self, data_mesh, shape):
        sharded = parallel_lib.shards_client_axis(data_mesh, shape[0])
        assert sharded
        ops = [op for op, _, _ in self._gather(data_mesh, shape, sharded)]
        assert not [op for op in ops if op.startswith("all-gather")]
        assert [op for op in ops if op.startswith("all-reduce")]

    @pytest.mark.parametrize("shape", PINNED_STACKS,
                             ids=["mnist_stack", "femnist_stack"])
    def test_slice_loop_all_gathers_a_sharded_stack(self, data_mesh, shape):
        assert [ty for op, ty, _ in self._gather(data_mesh, shape, False)
                if op.startswith("all-gather") and _is_whole_stack(ty, shape)]

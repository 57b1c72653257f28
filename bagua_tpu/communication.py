"""Communication substrate: process groups as device meshes + collectives.

TPU-native redesign of the reference's ``bagua/torch_api/communication.py``
(1.4k LoC) and its Rust/Aluminum/NCCL stack:

* The reference builds three NCCL communicators per process group — global,
  inter-node and intra-node (``communication.py:116-163``).  Here a
  :class:`BaguaProcessGroup` owns a ``jax.sharding.Mesh`` with two named axes,
  ``("inter", "intra")``; hierarchical communication is reduction over the
  ``intra`` axis followed by the ``inter`` axis, and the "global communicator"
  is simply both axes at once.  On real hardware ``intra`` should map to an
  ICI slice and ``inter`` to DCN.
* The reference's NCCL-unique-id rendezvous through a torch TCPStore
  (``communication.py:551-560``) maps to ``jax.distributed.initialize``.
* The reference's per-group high-priority CUDA stream + event dance
  (``communication.py:590-596``) has no analog: XLA issues collectives
  asynchronously and overlaps them with compute on its own.

Two collective surfaces are provided:

1. **In-step** (:func:`allreduce_inplace` et al. — suffix kept for API parity
   with reference ``communication.py:922-1000``): traced functions used inside
   a ``shard_map`` / ``pjit`` step over a group's mesh axes.  This is the hot
   path; algorithms compose these.
2. **Eager** (:func:`allreduce`, :func:`allgather`, ...): drop-in analogs of
   the reference's explicit collectives (``communication.py:573-1401``).
   They operate on *stacked per-rank* arrays: single-controller groups pass
   the full ``(group.size, ...)`` stack (JAX sees every rank's value at
   once); multi-host groups pass each process's *local view*
   ``(len(local_ranks(group)), ...)`` and get back their own ranks' results
   (assembled via ``make_array_from_process_local_data``).  Each output
   slice is what that rank would hold after the collective.
"""

import contextlib
import contextvars
import functools
import pickle
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from bagua_tpu.defs import ReduceOp
from bagua_tpu.mesh import MeshSpec
from bagua_tpu.observability.cold_start import cold_event

INTER_AXIS = "inter"
INTRA_AXIS = "intra"
ALL_AXES = (INTER_AXIS, INTRA_AXIS)

_default_group: Optional["BaguaProcessGroup"] = None


class BaguaProcessGroup:
    """A group of ranks arranged on a named device mesh.

    Without a ``mesh_spec`` this is the classic 2-D ``(inter, intra)`` mesh:
    ``intra_size`` ranks form the fast inner axis (ICI / one host);
    ``inter_size = size // intra_size`` forms the slower outer axis (DCN),
    and every axis carries the data-parallel exchange.

    With a :class:`bagua_tpu.mesh.MeshSpec` the mesh axes are the spec's
    named axes (e.g. ``dp × tp``): the engine's bucketed exchange rides the
    spec's *data* axes only, while *model* axes (tp/sp/ep/pp) are left to the
    model's own collectives.
    """

    def __init__(
        self,
        devices: Sequence,
        intra_size: Optional[int] = None,
        name: str = "bagua",
        mesh_spec: Optional[MeshSpec] = None,
    ):
        devices = list(devices)
        n = len(devices)
        self.name = name
        self.devices = devices
        self.mesh_spec = mesh_spec
        if mesh_spec is not None:
            if intra_size is not None:
                raise ValueError(
                    "pass either intra_size (legacy inter/intra mesh) or "
                    "mesh_spec (named mesh), not both"
                )
            self.mesh = Mesh(mesh_spec.device_array(devices), mesh_spec.names)
            # Legacy hierarchical split is undefined on a named mesh: the
            # whole group counts as one "intra" domain for consumers that
            # only read the attributes (hierarchical exchange itself is
            # fenced at DDP construction).
            self.intra_size = n
            self.inter_size = 1
            return
        if intra_size is None:
            # Default: devices-per-process (one host = one ICI domain).
            per_proc = max(1, n // max(jax.process_count(), 1))
            intra_size = per_proc if n % per_proc == 0 else n
        if n % intra_size != 0:
            raise ValueError(f"group size {n} not divisible by intra_size {intra_size}")
        self.intra_size = intra_size
        self.inter_size = n // intra_size
        self.mesh = Mesh(
            np.array(devices).reshape(self.inter_size, self.intra_size),
            ALL_AXES,
        )

    @property
    def size(self) -> int:
        return len(self.devices)

    @property
    def all_axes(self) -> Tuple[str, ...]:
        """Every mesh axis name (state stacks/shards over all of them)."""
        if self.mesh_spec is not None:
            return self.mesh_spec.names
        return ALL_AXES

    @property
    def data_axes(self) -> Tuple[str, ...]:
        """Axes the batch shards over and the gradient exchange rides."""
        if self.mesh_spec is not None:
            return self.mesh_spec.data_axes
        return ALL_AXES

    @property
    def model_axes(self) -> Tuple[str, ...]:
        if self.mesh_spec is not None:
            return self.mesh_spec.model_axes
        return ()

    @property
    def exchange_size(self) -> int:
        """Ranks in the gradient-exchange ring (== ``size`` unless model
        axes are present — then the exchange communicates only among ranks
        sharing a model-axis coordinate)."""
        if self.mesh_spec is not None:
            return self.mesh_spec.exchange_size
        return self.size

    @property
    def spans_processes(self) -> bool:
        """True when the group's devices live in more than one OS process
        (multi-host / multi-controller deployment)."""
        return len({d.process_index for d in self.devices}) > 1

    @property
    def ranks(self) -> List[int]:
        return list(range(self.size))

    def __repr__(self) -> str:
        if self.mesh_spec is not None:
            return f"BaguaProcessGroup(size={self.size}, mesh={self.mesh_spec!r})"
        return f"BaguaProcessGroup(size={self.size}, inter={self.inter_size}, intra={self.intra_size})"

    # ---- shard_map helpers -------------------------------------------------

    def shard_map(self, fn: Callable, in_specs, out_specs, check_vma: bool = False):
        """``jax.shard_map`` over this group's mesh."""
        return jax.shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma
        )


@cold_event("setup", "group")
def init_process_group(
    devices: Optional[Sequence] = None,
    intra_size: Optional[int] = None,
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    mesh_spec: Optional[MeshSpec] = None,
) -> BaguaProcessGroup:
    """Initialize the default process group (reference ``communication.py:446``).

    On multi-host deployments pass ``coordinator_address``/``num_processes``/
    ``process_id`` (or set the usual env) and this calls
    ``jax.distributed.initialize`` — the analog of the reference's
    torch-store/NCCL-unique-id rendezvous.  Single-host callers just get a
    mesh over the local devices.
    """
    global _default_group
    if coordinator_address is not None and not jax.distributed.is_initialized():
        # Must run before anything initializes the XLA backend (jax.distributed
        # requirement); callers on multi-host must call init_process_group first.
        # Skipped when the runtime is already up (e.g. re-initializing the
        # default group after a checkpoint-restart in the same process).
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    if devices is None:
        devices = jax.devices()
        if mesh_spec is not None:
            devices = devices[: mesh_spec.size]
    _default_group = BaguaProcessGroup(
        devices, intra_size=intra_size, mesh_spec=mesh_spec
    )
    return _default_group


def is_initialized() -> bool:
    return _default_group is not None


def get_default_group() -> BaguaProcessGroup:
    if _default_group is None:
        init_process_group()
    return _default_group  # type: ignore


def new_group(
    ranks: Optional[Sequence[int]] = None,
    intra_size: Optional[int] = None,
    mesh_spec: Optional[MeshSpec] = None,
) -> BaguaProcessGroup:
    """Create a new group from ranks of the default group
    (reference ``communication.py:217``)."""
    base = get_default_group()
    if ranks is None:
        devices = base.devices
    else:
        devices = [base.devices[r] for r in ranks]
    return BaguaProcessGroup(devices, intra_size=intra_size, mesh_spec=mesh_spec)


# ---------------------------------------------------------------------------
# In-step collectives (call inside shard_map over a group's mesh axes)
# ---------------------------------------------------------------------------


# The ambient axes an ``axis=None`` collective resolves to.  The engine
# enters :func:`default_axes` inside its shard_map body (the body executes
# during tracing, so the context is live for exactly that trace): on a
# named mesh the algorithm's collectives then ride the group's data axes
# while explicit-axis collectives (the model's tp/sp/ep exchanges) are
# untouched.  Outside any context the legacy ALL_AXES default applies.
_DEFAULT_AXES: contextvars.ContextVar = contextvars.ContextVar(
    "bagua_default_axes", default=None
)


@contextlib.contextmanager
def default_axes(axes: Sequence[str]):
    """Make ``axes`` the resolution of ``axis=None`` collectives within."""
    token = _DEFAULT_AXES.set(tuple(axes))
    try:
        yield
    finally:
        _DEFAULT_AXES.reset(token)


def _axes(axis) -> Tuple[str, ...]:
    if axis is None:
        ambient = _DEFAULT_AXES.get()
        return ambient if ambient is not None else ALL_AXES
    if isinstance(axis, str):
        return (axis,)
    return tuple(axis)


def rank_id(axis=None) -> jnp.ndarray:
    """Linear rank of the caller within the given axes (row-major)."""
    axes = _axes(axis)
    r = jnp.zeros((), jnp.int32)
    for a in axes:
        r = r * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return r


def axis_size(axis=None) -> int:
    axes = _axes(axis)
    n = 1
    for a in axes:
        n *= jax.lax.axis_size(a)
    return n


def allreduce_inplace(x: jnp.ndarray, op: ReduceOp = ReduceOp.AVG, axis=None) -> jnp.ndarray:
    """Allreduce of the local view over the group axes
    (reference ``communication.py:922``)."""
    axes = _axes(axis)
    op = ReduceOp(op)
    if op == ReduceOp.SUM:
        return jax.lax.psum(x, axes)
    if op == ReduceOp.AVG:
        return jax.lax.pmean(x, axes)
    if op == ReduceOp.MAX:
        return jax.lax.pmax(x, axes)
    if op == ReduceOp.MIN:
        return jax.lax.pmin(x, axes)
    if op == ReduceOp.PRODUCT:
        # No pprod primitive: log-sum-exp trick fails for negatives; use gather.
        gathered = jax.lax.all_gather(x, axes, tiled=False)
        return jnp.prod(gathered.reshape((-1,) + x.shape), axis=0)
    if op in (ReduceOp.BOR, ReduceOp.BAND, ReduceOp.BXOR):
        gathered = jax.lax.all_gather(x, axes, tiled=False).reshape((-1,) + x.shape)
        red = {
            ReduceOp.BOR: jnp.bitwise_or,
            ReduceOp.BAND: jnp.bitwise_and,
            ReduceOp.BXOR: jnp.bitwise_xor,
        }[op]
        out = gathered[0]
        for i in range(1, gathered.shape[0]):
            out = red(out, gathered[i])
        return out
    raise ValueError(f"unsupported op {op}")


def allgather_inplace(x: jnp.ndarray, axis=None, tiled: bool = False) -> jnp.ndarray:
    return jax.lax.all_gather(x, _axes(axis), tiled=tiled)


def reduce_scatter_inplace(x: jnp.ndarray, op: ReduceOp = ReduceOp.SUM, axis=None) -> jnp.ndarray:
    """Reduce-scatter a flat array: returns this rank's 1/n chunk of the
    reduction (reference ``communication.py:1219`` reducescatter)."""
    axes = _axes(axis)
    op = ReduceOp(op)
    if op not in (ReduceOp.SUM, ReduceOp.AVG):
        raise ValueError("reduce_scatter supports SUM/AVG")
    out = jax.lax.psum_scatter(x, axes, scatter_dimension=0, tiled=True)
    if op == ReduceOp.AVG:
        out = out / axis_size(axes)
    return out


def broadcast_inplace(x: jnp.ndarray, src_rank: int = 0, axis=None) -> jnp.ndarray:
    """Broadcast rank ``src_rank``'s local view to all ranks."""
    axes = _axes(axis)
    me = rank_id(axes)
    masked = jnp.where(me == src_rank, x, jnp.zeros_like(x))
    return jax.lax.psum(masked, axes)


def alltoall_inplace(x: jnp.ndarray, axis=None) -> jnp.ndarray:
    """All-to-all of the leading dim (must divide by group size)."""
    axes = _axes(axis)
    return jax.lax.all_to_all(x, axes, split_axis=0, concat_axis=0, tiled=True)


def alltoall_v_inplace(x: jnp.ndarray, send_counts: jnp.ndarray, axis=None):
    """Variable-count all-to-all (reference ``alltoall_v``,
    ``communication.py:1263``), in the static-shape idiom XLA requires.

    Args:
        x: ``(n, capacity, ...)`` — chunk j (padded to ``capacity``) goes to
           rank j; only the first ``send_counts[j]`` rows of chunk j are
           meaningful.
        send_counts: ``(n,)`` int array — may differ per rank (it is data,
           not shape).

    Returns:
        ``(recv, recv_counts)``: ``recv[j]`` is the (padded) chunk received
        from rank j, valid up to ``recv_counts[j]`` rows.
    """
    axes = _axes(axis)
    n = axis_size(axes)
    if x.shape[0] != n:
        raise ValueError(f"leading dim {x.shape[0]} != group size {n}")
    recv = jax.lax.all_to_all(x, axes, split_axis=0, concat_axis=0, tiled=True)
    recv = recv.reshape((n,) + x.shape[1:])
    recv_counts = jax.lax.all_to_all(
        send_counts.reshape(n, 1), axes, split_axis=0, concat_axis=0, tiled=True
    ).reshape(n)
    return recv, recv_counts


def ppermute_apply(x: jnp.ndarray, perm, axis=None) -> jnp.ndarray:
    """Apply an explicit (src, dst) permutation over the (possibly combined)
    group axes — one point-to-point ``collective-permute``, never a gather.

    ``lax.ppermute`` accepts the combined axes tuple directly, with ranks
    flattened row-major (inter major, intra minor) — exactly this module's
    rank convention — so arbitrary cross-axis routes lower to a single
    XLA collective-permute riding ICI/DCN point-to-point.  Like
    ``lax.ppermute``, destinations absent from ``perm`` receive zeros."""
    axes = _axes(axis)
    return jax.lax.ppermute(x, axes[0] if len(axes) == 1 else axes, perm)


def ppermute_shift(x: jnp.ndarray, shift: int, axis=None) -> jnp.ndarray:
    """Ring shift: rank i receives rank (i - shift) mod n's value (ranks
    row-major over the combined axes).  One collective-permute."""
    axes = _axes(axis)
    n = axis_size(axes)
    shift = shift % n
    perm = [(i, (i + shift) % n) for i in range(n)]
    return ppermute_apply(x, perm, axes)


def hierarchical_allreduce_inplace(x: jnp.ndarray, op: ReduceOp = ReduceOp.AVG) -> jnp.ndarray:
    """Intra-axis reduce, then inter-axis reduce (reference hierarchical
    communicator, ``communicators/mod.rs:262-446``).  Numerically identical to
    a flat allreduce but keeps the two phases separate so algorithms can
    compress between them."""
    op = ReduceOp(op)
    if op == ReduceOp.AVG:
        x = allreduce_inplace(x, op=ReduceOp.SUM, axis=INTRA_AXIS)
        x = allreduce_inplace(x, op=ReduceOp.SUM, axis=INTER_AXIS)
        n = axis_size(ALL_AXES)
        return jax.tree.map(lambda l: l / n, x)  # x may be a pytree (tuple fusion)
    # SUM/MAX/MIN/PRODUCT/bitwise all compose associatively across phases.
    x = allreduce_inplace(x, op=op, axis=INTRA_AXIS)
    return allreduce_inplace(x, op=op, axis=INTER_AXIS)


# ---------------------------------------------------------------------------
# Eager collectives over stacked (size, ...) arrays
# ---------------------------------------------------------------------------


# Jitted eager-collective cache: (mesh, key) -> compiled callable.  Without
# this every eager call would rebuild a closure and re-trace (~80x overhead).
_EAGER_CACHE: dict = {}


def _eager_compiled(group: BaguaProcessGroup, key: tuple, make_fn: Callable):
    cache_key = (group.mesh, key)
    cached = _EAGER_CACHE.get(cache_key)
    if cached is None:
        fn = make_fn()
        axes = group.all_axes

        def per_rank(x):
            # eager collectives span the WHOLE group, whatever its axes are
            # named (the body runs at trace time, so the context is live for
            # the axis=None resolution inside fn)
            with default_axes(axes):
                return fn(x[0])[None]

        cached = jax.jit(
            group.shard_map(per_rank, in_specs=P(axes), out_specs=P(axes))
        )
        _EAGER_CACHE[cache_key] = cached
    return cached


def local_ranks(group: Optional[BaguaProcessGroup] = None) -> List[int]:
    """Ranks of ``group`` whose devices this process owns, in rank order —
    the order of the slices this process passes to (and receives from) the
    eager collectives on a multi-host group."""
    group = group or get_default_group()
    me = jax.process_index()
    return [r for r, d in enumerate(group.devices) if d.process_index == me]


def _eager(group: Optional[BaguaProcessGroup], key: tuple, make_fn: Callable):
    """Lift ``make_fn()(local_value) -> local_value`` over stacked per-rank
    arrays.  The stacked leading axis is sharded over the mesh, so each
    rank's local block is ``(1, ...)``; we strip/restore that axis around the
    collective.  Compiled callables are cached per ``(mesh, key)`` (jit
    handles shape/dtype polymorphism internally).

    **Single-controller groups** take and return the full ``(size, ...)``
    stack — the caller sees every rank's value at once.

    **Multi-host groups** (reference explicit collectives work across nodes,
    ``communication.py:573-1401``) take the *local view*: each process passes
    a ``(n_local_ranks, ...)`` array holding the send values for its own
    ranks (order :func:`local_ranks`) and receives back a numpy array with
    its own ranks' results.  The stacks are assembled into one global array
    with ``jax.make_array_from_process_local_data`` — every process in the
    group must call collectives in the same order (the usual SPMD
    contract)."""
    group = group or get_default_group()
    compiled = _eager_compiled(group, key, make_fn)
    if not group.spans_processes:
        return compiled

    # The local-view wrapper is cached alongside the compiled fn — rebuilding
    # the sharding and rescanning group.devices per call would put O(devices)
    # python work on the eager hot path.
    cache_key = (group.mesh, key, "local_view")
    cached = _EAGER_CACHE.get(cache_key)
    if cached is not None:
        return cached

    from jax.sharding import NamedSharding

    sharding = NamedSharding(group.mesh, P(group.all_axes))
    n_local = len(local_ranks(group))

    def call_local_view(local):
        local = np.asarray(local)
        if local.shape[0] != n_local:
            raise ValueError(
                f"multi-host eager collective: expected this process's "
                f"({jax.process_index()}) local stack of shape ({n_local}, ...) "
                f"for its {n_local} rank(s), got {local.shape}"
            )
        global_shape = (group.size,) + local.shape[1:]
        garr = jax.make_array_from_process_local_data(sharding, local, global_shape)
        out = compiled(garr)
        shards = sorted(
            out.addressable_shards, key=lambda s: s.index[0].start or 0
        )
        return np.concatenate([np.asarray(s.data) for s in shards], axis=0)

    _EAGER_CACHE[cache_key] = call_local_view
    return call_local_view


def allreduce(send, op: ReduceOp = ReduceOp.AVG, comm: Optional[BaguaProcessGroup] = None):
    """Eager allreduce (reference ``communication.py:848``). ``send`` is a
    stacked per-rank array: ``(group.size, ...)`` on a single-controller
    group, or this process's ``(len(local_ranks(group)), ...)`` local view on
    a multi-host group (see :func:`_eager`)."""
    op = ReduceOp(op)
    return _eager(
        comm, ("allreduce", op), lambda: functools.partial(allreduce_inplace, op=op)
    )(send)


def allgather(send, comm: Optional[BaguaProcessGroup] = None):
    """Each output slice is the concatenation of every rank's slice
    (reference ``communication.py:1038``).  ``send`` as in :func:`allreduce`
    (local view on multi-host groups)."""
    return _eager(
        comm, ("allgather",), lambda: functools.partial(allgather_inplace, tiled=True)
    )(send)


def reducescatter(send, op: ReduceOp = ReduceOp.SUM, comm: Optional[BaguaProcessGroup] = None):
    op = ReduceOp(op)
    return _eager(
        comm, ("reducescatter", op), lambda: functools.partial(reduce_scatter_inplace, op=op)
    )(send)


def broadcast(send, src: int = 0, comm: Optional[BaguaProcessGroup] = None):
    """Broadcast rank ``src``'s slice to every rank
    (reference ``communication.py:573``)."""
    return _eager(
        comm, ("broadcast", src), lambda: functools.partial(broadcast_inplace, src_rank=src)
    )(send)


def alltoall(send, comm: Optional[BaguaProcessGroup] = None):
    """Reference ``communication.py:1100`` alltoall: each rank's slice is
    split into ``size`` chunks and chunk j goes to rank j."""
    return _eager(comm, ("alltoall",), lambda: alltoall_inplace)(send)


def reduce(send, dst: int = 0, op: ReduceOp = ReduceOp.AVG, comm: Optional[BaguaProcessGroup] = None):
    """Reduce to rank ``dst``; other ranks keep their input
    (reference ``communication.py:958``)."""
    op = ReduceOp(op)

    def make():
        def fn(x):
            red = allreduce_inplace(x, op=op)
            return jnp.where(rank_id() == dst, red, x)

        return fn

    return _eager(comm, ("reduce", op, dst), make)(send)


def scatter(send, src: int = 0, comm: Optional[BaguaProcessGroup] = None):
    """Rank ``src``'s slice is chunked across ranks; rank i's output is chunk i
    (reference ``communication.py:1155``)."""

    def make():
        def fn(x):
            n = axis_size()
            full = broadcast_inplace(x, src_rank=src)
            chunks = jnp.reshape(full, (n, x.shape[0] // n) + x.shape[1:])
            return jnp.take(chunks, rank_id(), axis=0)

        return fn

    return _eager(comm, ("scatter", src), make)(send)


def gather(send, dst: int = 0, comm: Optional[BaguaProcessGroup] = None):
    """All slices concatenated at rank ``dst``.

    The reference (``communication.py:1081``) leaves the recv buffer on
    non-dst ranks untouched; XLA's uniform output shape forces *some* value
    there, so non-dst ranks receive **zeros** — an unmistakable "no data"
    (matching ``lax.ppermute``'s convention for absent sources) rather than
    fabricated values a caller could mistake for a real gather result."""

    def make():
        def fn(x):
            g = allgather_inplace(x, tiled=True)
            return jnp.where(rank_id() == dst, g, jnp.zeros_like(g))

        return fn

    return _eager(comm, ("gather", dst), make)(send)


def barrier(comm: Optional[BaguaProcessGroup] = None):
    """Barrier as a tiny allreduce (reference ``communication.py:1377-1401``).

    Needs no caller-supplied per-rank data, so unlike the other eager
    collectives it also works on multi-host groups (via a cross-process
    device sync there)."""
    group = comm or get_default_group()
    if group.spans_processes:
        procs = {d.process_index for d in group.devices}
        if len(procs) == jax.process_count():
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("bagua_tpu_barrier")
            return
        # Group-scoped: a tiny collective over the group's own mesh, so
        # processes OUTSIDE the group are not involved (a global sync here
        # would deadlock against them).
        from jax.sharding import NamedSharding

        sharding = NamedSharding(group.mesh, P(group.all_axes))
        n_local = sum(
            1 for d in group.devices if d.process_index == jax.process_index()
        )
        token = jax.make_array_from_process_local_data(
            sharding, np.ones((n_local, 1), np.float32)
        )
        out = jax.jit(
            jnp.sum, out_shardings=NamedSharding(group.mesh, P())
        )(token)
        jax.block_until_ready(out)
        return
    token = jnp.ones((group.size, 1), jnp.float32)
    jax.block_until_ready(allreduce(token, op=ReduceOp.SUM, comm=group))


def broadcast_object(obj, src: int = 0):
    """Broadcast a picklable object across hosts (reference
    ``communication.py:668`` pickles into a ByteTensor).  Single-process: no-op."""
    if jax.process_count() == 1:
        return obj
    from jax.experimental import multihost_utils

    payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
    # broadcast_one_to_all always ships process 0's value, so gather instead
    # and select ``src``'s entry on every process.
    sizes = multihost_utils.process_allgather(np.array([payload.size], np.int64))
    n = int(np.asarray(sizes).reshape(-1)[src])
    buf = np.zeros(n, np.uint8)
    if jax.process_index() == src:
        buf[:] = payload
    data = multihost_utils.process_allgather(buf)
    return pickle.loads(np.asarray(data).reshape(jax.process_count(), n)[src].tobytes())

#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # on a TPU host: every chip it is given
    python chip_smoke.py --dry-run  # toy width, CPU, kernels interpreted

One process, no children.  It trains BERT-Large (hidden 1024 x 24 layers,
sequence 128, batch 32 per chip, bf16 compute, random weights from a seed,
one fixed synthetic batch) through ``init_process_group`` -> ``Trainer`` ->
``init_state`` -> ``fit``, first with ``gradient_allreduce`` and then with
``bytegrad``; on more than one chip it checks that state, batch and
collectives really span the chips, and trains a two-layer cut of the model
with ``ByteGradAlgorithm(hierarchical=False)`` (the default, hierarchical
form compresses nothing inside one host) to see 8-bit payloads on the wire;
it compiles every Pallas entry point
through Mosaic and compares it with its jnp oracle; and it captures a device
trace through ``Trainer(profile_dir=...)``.  Each phase prints one line; the
first phase that fails ends the process with its exception (no handler lets
a failed phase pass).  The last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``, with the device as JAX reports it.

Without ``--dry-run`` the platform is pinned to ``tpu`` before first device
use, so a missing chip is JAX's error and a non-zero exit, never a silent
CPU run.  Timings printed here are smoke timings (one run, compile
included where labelled), not results.  Nothing is written outside
``--out`` (default ``chiprun_out/chip_smoke``) and the compile cache.
"""

import argparse
import dataclasses
import contextlib
import functools
import glob
import json
import os
import re
import shutil
import sys
import time
from typing import Callable

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 0
STEPS = 8           # per algorithm, the compile step included
TRACE_STEPS = 2
FLAT_LAYERS, FLAT_VOCAB = 2, 2048   # the flat-bytegrad run's cut (more than one chip only)
SEQ = 128
BATCH_PER_CHIP = 32
LEARNING_RATE = 1e-2


def _say(dry: bool, line: str) -> None:
    print(("DRY RUN " if dry else "") + line, flush=True)


# ---------------------------------------------------------------------------
# train:<algorithm>
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Run:
    name: str
    trainer: object
    state: object
    batch: tuple


def _bert_config(dry: bool):
    import jax.numpy as jnp

    from bagua_tpu.models.bert import BertConfig, bert_large_config

    if dry:
        return BertConfig(
            vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
            intermediate_size=128, max_position_embeddings=16,
            compute_dtype=jnp.bfloat16,
        )
    return bert_large_config(compute_dtype=jnp.bfloat16, max_position_embeddings=SEQ)


def setup_run(name, algorithm, model, params, host_batch, group, profile_dir=None) -> Run:
    import optax

    from bagua_tpu.models.bert import mlm_loss_fn
    from bagua_tpu.trainer import Trainer

    trainer = Trainer(
        mlm_loss_fn(model), optax.sgd(LEARNING_RATE), algorithm,
        process_group=group, profile_dir=profile_dir,
        # the capture window opens in the fit() call of the trace phase
        profile_steps=(STEPS, STEPS + TRACE_STEPS),
    )
    state = trainer.init_state(params)
    return Run(name, trainer, state, trainer.ddp.shard_batch(host_batch))


def _mean_loss(trainer) -> float:
    import numpy as np

    return float(np.mean(np.asarray(trainer.last_losses)))


def _repeat(batch):
    while True:
        yield batch


def train(run: Run, layers: int, dry: bool) -> None:
    import jax
    import numpy as np

    trainer = run.trainer
    t0 = time.perf_counter()
    run.state = trainer.fit(run.state, _repeat(run.batch), n_steps=1, log_every=0)
    jax.block_until_ready(run.state)
    compile_s = time.perf_counter() - t0
    first = _mean_loss(trainer)
    t0 = time.perf_counter()
    run.state = trainer.fit(run.state, _repeat(run.batch), n_steps=1, log_every=0)
    jax.block_until_ready(run.state)
    second_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    run.state = trainer.fit(
        run.state, _repeat(run.batch), n_steps=STEPS - 2, log_every=0
    )
    jax.block_until_ready(run.state)
    steady_s = (time.perf_counter() - t0) / (STEPS - 2)
    last = _mean_loss(trainer)
    if not (np.isfinite(first) and np.isfinite(last)):
        raise AssertionError(f"{run.name}: loss not finite ({first} -> {last})")
    if not last < first:
        raise AssertionError(f"{run.name}: loss did not fall ({first} -> {last})")
    ddp = trainer.ddp
    group = ddp.group
    # ranks the 8-bit leg spans: hierarchical bytegrad compresses the inter
    # axis only, and every single-process group has inter_size == 1
    compressed = ""
    if run.name.startswith("bytegrad"):
        ranks = (group.inter_size
                 if ddp.impl.hierarchical and group.intra_size > 1 else group.size)
        compressed = f" compressed_leg_ranks={ranks}" + (
            "(nothing_is_compressed)" if ranks == 1 else "")
    _say(dry, (
        f"phase=train:{run.name} ok layers={layers} steps={STEPS} loss_first={first:.4f} "
        f"loss_last={last:.4f} buckets={ddp.plan.num_buckets} "
        f"overlap={ddp.overlap_enabled}{compressed} "
        f"smoke_compile_plus_first_step_s={compile_s:.1f} "
        f"smoke_second_step_s={second_s:.3f} smoke_steady_step_s={steady_s:.4f}"
    ))


# ---------------------------------------------------------------------------
# multichip
# ---------------------------------------------------------------------------

_COLLECTIVE = re.compile(
    r"\b(all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute)"
    r"(-start)?\("
)


def _collectives(hlo_text: str):
    """``(op, result types, line)`` of every collective instruction."""
    for line in hlo_text.splitlines():
        m = _COLLECTIVE.search(line)
        if m is not None and "=" in line[: m.start()]:
            yield m.group(1), line[line.index("=") + 1: m.start()], line


def collective_census(hlo_text: str, n_devices: int) -> dict:
    """``{op: [participants per instruction]}`` from compiled HLO text.  A
    collective's participants are the size of one replica group (the explicit
    ``{{0,1,2,3}}`` form, or the last dimension of the iota form
    ``[g,k]<=[n]``; an empty ``{}`` means every device), or for a
    collective-permute the number of distinct devices in its pairs."""
    out = {}
    for op, _, line in _collectives(hlo_text):
        if op == "collective-permute":
            pairs = re.search(r"source_target_pairs=\{([0-9,{} ]*)\}", line)
            width = len(set(re.findall(r"\d+", pairs.group(1)))) if pairs else 0
        else:
            iota = re.search(r"replica_groups=\[([0-9,]+)\]<=", line)
            explicit = re.search(r"replica_groups=\{(\{[0-9, ]*\})?", line)
            if iota:
                width = int(iota.group(1).split(",")[-1])
            elif explicit and explicit.group(1):
                width = len(re.findall(r"\d+", explicit.group(1)))
            else:
                width = n_devices
        out.setdefault(op, []).append(width)
    return out


def collective_payloads(hlo_text: str) -> dict:
    """``{(op, dtype): [result bytes per instruction]}`` from compiled HLO
    text: what each collective moves, by element type (``u8[4,4,14464]`` is
    231,424 bytes; the elements of a tuple result are added up)."""
    out = {}
    for op, result, _ in _collectives(hlo_text):
        moved = {}
        for dtype, bits, dims in re.findall(r"\b([a-z]+)(\d+)\[([0-9,]*)\]", result):
            numel = 1
            for d in filter(None, dims.split(",")):
                numel *= int(d)
            moved[dtype + bits] = moved.get(dtype + bits, 0) + numel * int(bits) // 8
        for dtype, nbytes in moved.items():
            out.setdefault((op, dtype), []).append(nbytes)
    return out


def check_placement(run: Run, n: int) -> str:
    """Before the first step: the state is spread 1/n per device and the
    batch carries the step's data sharding."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    group = run.trainer.ddp.group
    if group.size != n:
        raise AssertionError(f"group.size={group.size} != {n} devices")
    state_bytes = 0
    for leaf in jax.tree.leaves(run.state):
        devices = {s.device for s in leaf.addressable_shards}
        if len(devices) != n:
            raise AssertionError(f"state leaf {leaf.shape} on {len(devices)} devices")
        for s in leaf.addressable_shards:
            if s.data.shape[0] * n != leaf.shape[0]:
                raise AssertionError(
                    f"a device holds {s.data.shape[0]}/{leaf.shape[0]} of a "
                    f"rank-stacked leaf, expected 1/{n}"
                )
        state_bytes += leaf.nbytes
    share = state_bytes // n
    jax.block_until_ready(run.state)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in group.devices]
    if all(b is not None for b in in_use):
        # the state, the batch and allocator slack: nothing else is resident,
        # and nothing is collected first (a Trainer user's process is not)
        if max(in_use) > share + (64 << 20):
            raise AssertionError(f"bytes_in_use {in_use} vs state share {share}")
        mem = f"bytes_in_use_max={max(in_use)} state_share={share}"
    else:
        mem = f"bytes_in_use=unreported state_share={share}"
    want = NamedSharding(group.mesh, P(group.data_axes))
    for leaf in jax.tree.leaves(run.batch):
        if not leaf.sharding.is_equivalent_to(want, leaf.ndim):
            raise AssertionError(f"batch leaf sharded {leaf.sharding}, want {want}")
        if len({s.device for s in leaf.addressable_shards}) != n:
            raise AssertionError("batch does not span every device")
    return mem


def check_after_steps(run: Run, n: int) -> str:
    """After the steps: replicas bitwise equal, and the compiled step's
    collectives span all chips.  Prints all-reduce count against bucket
    count — a fact for the overlap design, not a gate."""
    import jax
    import jax.numpy as jnp

    def replicas_equal(params):
        def eq(x):
            bits = jax.lax.bitcast_convert_type(x, f"uint{x.dtype.itemsize * 8}")
            return jnp.all(bits == bits[:1])

        return jnp.all(jnp.stack([eq(x) for x in jax.tree.leaves(params)]))

    if not bool(jax.jit(replicas_equal)(run.state.params)):
        raise AssertionError("replica parameters differ across ranks")
    ddp = run.trainer.ddp
    census = spanning_census(compiled_step_text(run), n)
    counts = " ".join(f"{op}={len(w)}" for op, w in sorted(census.items()))
    return (
        f"replicas_bitwise_equal=True collectives_span={n} {counts} "
        f"allreduce_ops={len(census.get('all-reduce', []))} "
        f"plan_buckets={ddp.plan.num_buckets} overlap={ddp.overlap_enabled}"
    )


def compiled_step_text(run: Run) -> str:
    """The text of the step the run trained with: of the executable itself."""
    return run.trainer.ddp.compiled_step().as_text()


def spanning_census(text: str, n: int) -> dict:
    census = collective_census(text, n)
    if not census:
        raise AssertionError("compiled step holds no collective")
    narrow = {op: w for op, w in census.items() if min(w) != n}
    if narrow:
        raise AssertionError(f"collectives over fewer than {n} chips: {narrow}")
    return census


def check_compressed_wire(run: Run, n: int) -> str:
    """Flat bytegrad's compiled step moves every bucket as 8-bit payloads
    over all chips: a u8 all-to-all (scatter) and a u8 all-gather per bucket
    at least, and next to nothing in any wider type (the min/max sidecars)."""
    text = compiled_step_text(run)
    spanning_census(text, n)
    payloads = collective_payloads(text)
    buckets = run.trainer.ddp.plan.num_buckets
    u8 = {op: b for (op, dtype), b in payloads.items() if dtype == "u8"}
    for op in ("all-to-all", "all-gather"):
        if len(u8.get(op, [])) < buckets:
            raise AssertionError(
                f"{len(u8.get(op, []))} u8 {op} ops for {buckets} buckets: "
                f"{ {k: len(v) for k, v in payloads.items()} }")
    u8_bytes = sum(map(sum, u8.values()))
    other_bytes = sum(sum(b) for (_, dtype), b in payloads.items() if dtype != "u8")
    if other_bytes * 100 > u8_bytes:
        raise AssertionError(
            f"{other_bytes} bytes move uncompressed beside {u8_bytes} u8 bytes")
    return (
        f"collectives_span={n} u8_all_to_all={len(u8['all-to-all'])} "
        f"u8_all_gather={len(u8['all-gather'])} plan_buckets={buckets} "
        f"collective_result_bytes_u8={u8_bytes} collective_result_bytes_other={other_bytes}"
    )


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class KernelCase:
    """One ``*_pallas`` entry point at one shape the wrapper admits.
    ``pallas(*args, interpret=...)`` and ``oracle(*args)`` return matching
    tuples; ``exact`` lists the outputs that must match bitwise, the rest
    are held to ``rtol``/``atol`` as ``assert_allclose`` reads them, the
    bound of the kernel's own unit test.

    ``f32_matmul`` marks a kernel that multiplies f32 matrices.  That bound
    is one for f32 arithmetic, and at the default precision the chip runs an
    f32 matmul in bf16 passes, in the oracle as in the kernel, so the two
    orders of accumulation round apart.  Such a kernel is held to its bound
    with kernel and oracle at the highest precision; at the default
    precision, which is what training runs, it is compiled and run as well,
    its difference printed and held to ``DEFAULT_PRECISION_TOL`` of the
    oracle's largest magnitude."""

    kernel: str
    shape: str
    make_args: Callable
    pallas: Callable
    oracle: Callable
    exact: tuple = ()
    rtol: float = 1e-5
    atol: float = 1e-5
    f32_matmul: bool = False


DEFAULT_PRECISION_TOL = 2e-2


def _randn(seed, *shape):
    import jax.numpy as jnp
    import numpy as np

    return jnp.asarray(np.random.RandomState(seed).randn(*shape).astype(np.float32))


def kernel_cases(dry: bool):
    """Chip shapes come from the models in ``models/``: BERT-Large's bucket
    plan on four ranks (a 1024x4096 FFN kernel alone in its bucket gives a
    1,048,576-element chunk; a 1024x1024 attention projection gives
    262,144), its attention at batch 32 x sequence 128 (head dim 64), and
    the Llama bench shape (GQA 12q/4kv, head dim 128, sequence 1024).  The
    dry run keeps every wrapper on its kernel path at toy sizes."""
    import jax
    import jax.numpy as jnp

    from bagua_tpu.kernels import collective_matmul as cm
    from bagua_tpu.kernels import flash_attention as fa
    from bagua_tpu.kernels import minmax_uint8 as mm
    from bagua_tpu.kernels import quantized_ring as qr

    n = 4
    chunk, small_chunk = (8192, 4096) if dry else (1048576, 262144)
    cases = [
        KernelCase(
            "compress_minmax_uint8_pallas", f"({n},{chunk})",
            lambda: (_randn(1, n, chunk),),
            mm.compress_minmax_uint8_pallas, mm.compress_minmax_uint8,
            exact=(0,),
        ),
        # four chunks to a grid step: the block form Mosaic first refused
        KernelCase(
            "compress_minmax_uint8_pallas", f"({n},{small_chunk})",
            lambda: (_randn(11, n, small_chunk),),
            mm.compress_minmax_uint8_pallas, mm.compress_minmax_uint8,
            exact=(0,),
        ),
        KernelCase(
            "decompress_minmax_uint8_pallas", f"({n},{chunk})",
            lambda: mm.compress_minmax_uint8(_randn(2, n, chunk)),
            lambda q, m, interpret: (
                mm.decompress_minmax_uint8_pallas(q, m, interpret=interpret),),
            lambda q, m: (mm.decompress_minmax_uint8(q, m),),
        ),
        KernelCase(
            "decompress_reduce_requantize_pallas", f"({n},{small_chunk})",
            lambda: mm.compress_minmax_uint8(_randn(3, n, small_chunk)),
            mm.decompress_reduce_requantize_pallas, mm.decompress_reduce_requantize,
            exact=(0,),
        ),
    ]
    for bits, block in ((8, 4096), (4, 8192)):
        nblocks = 4 if dry else chunk // block

        def hop_args(bits=bits, block=block, nblocks=nblocks):
            q, m = qr._compressors(bits)[0](_randn(4 + bits, nblocks, block))
            return q, m, _randn(5 + bits, nblocks, block)

        cases.append(KernelCase(
            f"hop_dequant_add_requant_pallas(bits={bits})", f"({nblocks},{block})",
            hop_args,
            functools.partial(qr.hop_dequant_add_requant_pallas, bits=bits),
            functools.partial(qr.hop_dequant_add_requant, bits=bits),
            exact=(0, 2),
        ))
    m_, k_, n_ = (64, 128, 128) if dry else (1024, 1024, 1024)
    cases.append(KernelCase(
        "matmul_tile_pallas", f"({m_},{k_})x({k_},{n_})",
        lambda: (_randn(6, m_, k_), _randn(7, k_, n_)),
        lambda x, w, interpret: (cm.matmul_tile_pallas(x, w, interpret=interpret),),
        lambda x, w: (jnp.dot(x, w),),
        exact=(0,),
    ))
    for d, (b, t, h, h_kv) in ((64, (32, 128, 16, 16)), (128, (4, 1024, 12, 4))):
        if dry:
            b, t, h, h_kv = 1, 128, 2, (2 if d == 64 else 1)

        def attn_args(d=d, b=b, t=t, h=h, h_kv=h_kv):
            mask = jnp.broadcast_to(jnp.tril(jnp.ones((t, t), bool)), (b, t, t))
            return (_randn(8, b, t, h, d) / d ** 0.5, _randn(9, b, t, h_kv, d),
                    _randn(10, b, t, h_kv, d), mask)

        def attn_oracle(q, k, v, mask, h=h, h_kv=h_kv):
            g = h // h_kv
            if g > 1:
                k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
            return fa.block_attention(q, k, v, mask)

        def normalized(o, l):
            # invariant to the row-max shift, so the fused backward's
            # stop-gradient-on-m is exact for it (flash_attention_bwd_pallas)
            return jnp.sum(jnp.sin(o / (l[..., None] + 1e-9)))

        def bwd_pallas(q, k, v, mask, interpret, oracle=attn_oracle):
            o, l, m = oracle(q, k, v, mask)
            do, dl = jax.grad(normalized, argnums=(0, 1))(o, l)
            return fa.flash_attention_bwd_pallas(
                q, k, v, mask, m, dl, do, interpret=interpret)

        def bwd_oracle(q, k, v, mask, oracle=attn_oracle):
            return jax.grad(
                lambda q, k, v: normalized(*oracle(q, k, v, mask)[:2]),
                argnums=(0, 1, 2))(q, k, v)

        shape = f"b{b} t{t} h{h}/{h_kv} d{d}"
        cases.append(KernelCase(
            "block_attention_pallas", shape, attn_args,
            fa.block_attention_pallas, attn_oracle,
            rtol=3e-4, atol=3e-4, f32_matmul=True,  # tests/test_parallel.py
        ))
        cases.append(KernelCase(
            "flash_attention_bwd_pallas", shape, attn_args,
            bwd_pallas, bwd_oracle, rtol=3e-4, atol=3e-4, f32_matmul=True,
        ))
    return cases


def lowered_for_tpu(case: KernelCase, args) -> str:
    """The kernel's lowering for the TPU platform (front end only; works
    without a chip)."""
    import jax

    fn = jax.jit(functools.partial(case.pallas, interpret=False))
    return fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()


def run_kernel(case: KernelCase, dry: bool) -> None:
    import jax
    import numpy as np

    args = case.make_args()
    if "tpu_custom_call" not in lowered_for_tpu(case, args):
        raise AssertionError(
            f"{case.kernel} {case.shape}: no Mosaic custom call in the lowering "
            "(the wrapper returned its jnp composition)"
        )

    def both():
        got = jax.jit(functools.partial(case.pallas, interpret=dry))(*args)
        want = jax.jit(case.oracle)(*args)
        return [(np.asarray(g), np.asarray(w)) for g, w in zip(got, want, strict=True)]

    def max_abs_diff(pairs):
        return max(float(np.max(np.abs(g.astype(np.float64) - w))) for g, w in pairs)

    highest = case.f32_matmul and not dry  # the CPU multiplies f32 in f32
    with jax.default_matmul_precision("highest") if highest else contextlib.nullcontext():
        pairs = both()
    for i, (g, w) in enumerate(pairs):
        what = f"{case.kernel} {case.shape} output {i}"
        if i in case.exact:
            np.testing.assert_array_equal(g, w, err_msg=what)
        else:
            np.testing.assert_allclose(g, w, rtol=case.rtol, atol=case.atol, err_msg=what)
    line = f"kernel={case.kernel} shape={case.shape} mosaic=ok max_abs_diff={max_abs_diff(pairs):.3g}"
    if highest:
        pairs = both()
        diff = max_abs_diff(pairs)
        magnitude = max(float(np.max(np.abs(w))) for _, w in pairs)
        if not diff <= DEFAULT_PRECISION_TOL * magnitude:
            raise AssertionError(
                f"{case.kernel} {case.shape} at the default precision: max_abs_diff "
                f"{diff} against oracle magnitude {magnitude}")
        line += (f" precision=highest default_precision_max_abs_diff={diff:.3g}"
                 f" oracle_max_abs={magnitude:.3g}")
    _say(dry, line)


# ---------------------------------------------------------------------------
# trace
# ---------------------------------------------------------------------------


def trace(run: Run, profile_dir: str, dry: bool) -> None:
    import jax

    run.state = run.trainer.fit(
        run.state, _repeat(run.batch), n_steps=STEPS + TRACE_STEPS, log_every=0
    )
    jax.block_until_ready(run.state)
    paths = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise AssertionError(f"no .xplane.pb under {profile_dir}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    # the CPU backend runs its ops on host threads: the dry run accepts them
    prefix = "/host:CPU" if dry else "/device:TPU"
    planes = {
        p.name: sum(len(list(line.events)) for line in p.lines)
        for p in data.planes if p.name.startswith(prefix)
    }
    if not planes or not all(planes.values()):
        raise AssertionError(
            f"no {prefix} plane with events in {paths[0]}: "
            f"{[p.name for p in data.planes]}"
        )
    shutil.rmtree(profile_dir)
    _say(dry, (
        f"phase=trace ok steps={TRACE_STEPS} device_planes={len(planes)} "
        f"device_events={sum(planes.values())} trace_removed=True"
    ))


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run", action="store_true",
                    help="same phases at a toy width on the CPU, kernels interpreted")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out", "chip_smoke"))
    args = ap.parse_args(argv)
    dry = args.dry_run

    if dry and "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
        )
    sys.path.insert(0, HERE)
    import jax

    # Before first device use.  libtpu is installed wherever this runs, and
    # with the platform left open JAX falls back to the CPU by itself.
    jax.config.update("jax_platforms", "cpu" if dry else "tpu")

    import bagua_tpu

    if os.path.dirname(os.path.abspath(bagua_tpu.__file__)) != os.path.join(HERE, "bagua_tpu"):
        raise ImportError(
            f"bagua_tpu came from {bagua_tpu.__file__}, not from the checkout at {HERE}"
        )
    from bagua_tpu.env import setup_compile_cache

    cache_dir = setup_compile_cache()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != ("cpu" if dry else "tpu"):
        raise RuntimeError(f"wrong platform: {device}")
    import importlib.metadata

    import jaxlib

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "absent"

    _say(dry, (
        f"platform={device['platform']} device_kind={device['kind']!r} "
        f"devices={device['count']} jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu} compile_cache={cache_dir}"
    ))
    os.makedirs(args.out, exist_ok=True)
    profile_dir = os.path.join(args.out, "trace")
    shutil.rmtree(profile_dir, ignore_errors=True)

    import jax.numpy as jnp
    import numpy as np

    from bagua_tpu.models.bert import BertForPreTraining

    n = len(devices)
    group = bagua_tpu.init_process_group()
    cfg = _bert_config(dry)
    seq = cfg.max_position_embeddings

    def init_model(cfg):
        model = BertForPreTraining(cfg)
        # host copies: init_state sends each chip its replica from them
        return model, jax.device_get(jax.jit(
            lambda key: model.init(key, jnp.zeros((2, seq), jnp.int32))["params"]
        )(jax.random.PRNGKey(SEED)))

    model, params = init_model(cfg)
    rng = np.random.RandomState(SEED)
    global_batch = (4 if dry else BATCH_PER_CHIP) * n
    host_batch = tuple(
        rng.randint(0, cfg.vocab_size, (global_batch, seq)).astype(np.int32)
        for _ in range(2)
    )

    from bagua_tpu.algorithms import Algorithm
    from bagua_tpu.algorithms.bytegrad import ByteGradAlgorithm

    run = setup_run("gradient_allreduce", Algorithm.init("gradient_allreduce"),
                    model, params, host_batch, group)
    with run.trainer:
        placement = check_placement(run, n) if n > 1 else None
        train(run, cfg.num_layers, dry)
        if n > 1:
            _say(dry, f"phase=multichip ok group_size={n} {placement} "
                      f"batch_sharding=data {check_after_steps(run, n)}")
    del run

    run = setup_run("bytegrad", Algorithm.init("bytegrad"), model, params,
                    host_batch, group, profile_dir=profile_dir)
    with run.trainer:
        train(run, cfg.num_layers, dry)
        for case in kernel_cases(dry):
            run_kernel(case, dry)
        _say(dry, "phase=kernels ok")
        trace(run, profile_dir, dry)
    del run

    if n > 1:
        # The default bytegrad above is hierarchical and compresses the inter
        # axis only, which has one rank on every single-process group: it
        # ran gradient_allreduce's program.  The flat form is what puts 8-bit
        # payloads on the wire between the chips of one host.  Full width,
        # but two layers and a 2048-word vocabulary: at full size its cold
        # compile alone is 251 s (chip run, PR 21), of which the two 125 MB
        # embedding buckets cost about a third.
        flat_cfg = dataclasses.replace(
            cfg, num_layers=FLAT_LAYERS, vocab_size=min(FLAT_VOCAB, cfg.vocab_size))
        model, params = init_model(flat_cfg)
        host_batch = tuple(ids % flat_cfg.vocab_size for ids in host_batch)
        run = setup_run("bytegrad_flat", ByteGradAlgorithm(hierarchical=False),
                        model, params, host_batch, group)
        with run.trainer:
            train(run, flat_cfg.num_layers, dry)
            _say(dry, f"phase=multichip:compressed_wire ok vocab={flat_cfg.vocab_size} "
                      f"{check_compressed_wire(run, n)}")
        del run

    result = {"ok": True, "device": device}
    if dry:
        result["dry_run"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Shared harness for the driver-facing benchmark scripts (bench.py,
bench_bert.py, bench_moe.py, bench_scaling.py, bench_llama.py): the device
check, the persistent compilation cache, stderr progress notes and the
JSON-line emission protocol.

Contract (what the driver parses): every script prints JSON lines to stdout;
the LAST line is authoritative.  A script that finds no TPU exits non-zero
unless ``JAX_PLATFORMS=cpu`` asked for the CPU (a smoke of the script
itself), and an exception in the benchmark body is the process's exit: no
row is ever printed that a device did not produce.
"""

import json
import os
import sys
import time


class BenchHarness:
    def __init__(self, metric: str, unit: str):
        self.metric = metric
        self.unit = unit
        self.t0 = time.perf_counter()
        import jax

        from bagua_tpu.env import setup_compile_cache

        setup_compile_cache()
        platform = jax.devices()[0].platform
        if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
            sys.exit(
                f"{metric}: no TPU (jax.devices()[0].platform={platform!r}); "
                "set JAX_PLATFORMS=cpu to smoke the script on the CPU"
            )

    def note(self, msg: str) -> None:
        print(
            f"[{self.metric.split('_')[0]} +{time.perf_counter() - self.t0:5.1f}s] {msg}",
            file=sys.stderr,
            flush=True,
        )

    def emit(self, value: float, provisional: bool = False, extra: dict = None) -> None:
        line = {
            "metric": self.metric,
            "value": round(value, 2),
            "unit": self.unit,
        }
        if extra:
            line.update(extra)
        if provisional:
            line["provisional"] = True
        print(json.dumps(line), flush=True)

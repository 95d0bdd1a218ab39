"""A cell's files at the program's reduced size, for CPU rehearsals.

The harness refuses to run without a TPU; a rehearsal steers that here,
by handing ``harness.run_cell`` a stand-in device, and keeps JAX's
persistent compilation cache off.
"""

import contextlib
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import jax  # noqa: E402

import spec  # noqa: E402


class StandInTPU:
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


def reduced_cell(workload: str, **replace) -> spec.Cell:
    """The cell with its configuration at the program's reduced size, as
    its architecture module's ``reduced`` gives it."""
    cell = spec.load_cell(workload)
    fields = dict(cell.__dict__, config=cell.arch.reduced(cell.config))
    fields.update(replace)
    return spec.Cell(**fields)


# short answers, so a few seconds finish many requests
SHORT_TRAFFIC = {"prompt_len": 16,
                 "output_tokens": {"dist": "lognormal", "median": 8,
                                   "sigma": 0.5, "min": 4, "max": 16},
                 "arrivals": "backlog", "backlog_requests": 2048,
                 "flush_deadline_ms": None}


def short_cell(workload: str) -> spec.Cell:
    """The cell at reduced size under ``SHORT_TRAFFIC``, with its own
    limits; a run of a few seconds compares fewer tokens than a chip run
    does."""
    cell = spec.load_cell(workload)
    limits = dict(cell.cell["limits"], min_tokens_compared=50)
    return reduced_cell(workload, traffic=SHORT_TRAFFIC,
                        cell=dict(cell.cell, limits=limits))


@contextlib.contextmanager
def no_compile_cache():
    from repro.launch import compile_cache
    keep = compile_cache.enable_compile_cache
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    compile_cache.enable_compile_cache = lambda: "off"
    try:
        yield
    finally:
        compile_cache.enable_compile_cache = keep
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_s)

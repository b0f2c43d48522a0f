"""What every Pallas kernel here asks of the backend, in one place.

On a TPU backend a kernel is compiled by Mosaic or the call raises: no
kernel catches a lowering error to fall back to ``jax.numpy``. Off the
TPU (the CPU tests) the same ``pallas_call`` runs in interpret mode.
Which of the two a process got is visible in the compiled program text
(``tpu_custom_call``), which is what ``chip_smoke.py`` and
``tests/test_tpu_compile.py`` assert on.
"""

from __future__ import annotations

import collections

import jax
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from horovod_tpu.utils import env as env_mod

# Widest sublane tile over the dtypes the kernels see (8 rows for f32,
# 16 for bf16, 32 for int8): a block-row cap that is a multiple of this
# is legal for all of them.
SUBLANE_ROWS = 32


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_interpret() -> bool:
    """``HOROVOD_PALLAS_INTERPRET`` is the tests' switch; unset, interpret
    mode is used exactly when the backend is not a TPU."""
    return env_mod._get_bool("HOROVOD_PALLAS_INTERPRET", not on_tpu())


# What the serving engine's counters read of a kernel, declared once beside
# the kernel under the name it gives ``pallas_call``: the engine names no
# kernel and asks :func:`kernels_in` which of them its decode program holds.
# ``live_tiles(positions, cache_len)``: (tiles read, tiles of all rows,
# positions attended) of one leaf for a decode step at ``positions`` (numpy;
# a row at -1 is not active and runs at 0); ``writes_step``: the kernel puts
# the step's new columns into the cache (without ``live_tiles``: that
# alone); ``counts_positions``: its roofline counts bytes by the positions.
ServedKernel = collections.namedtuple(
    "ServedKernel", "live_tiles writes_step counts_positions",
    defaults=(None, False, False))
SERVED_KERNELS: dict = {}
# a reading a kernel's caller sows into ``kernel_stats``, by the name it is
# sown under -> where the value goes once the engine has it on the host
KERNEL_STATS: dict = {}


def kernels_in(jaxpr) -> list:
    """The ``name`` of every ``pallas_call`` in a (closed) jaxpr, nested
    calls included: what a traced program holds, before any compile."""
    names = []
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
            continue
        for value in eqn.params.values():
            inner = value if isinstance(value, (tuple, list)) else (value,)
            names.extend(name for sub in inner
                         if hasattr(getattr(sub, "jaxpr", sub), "eqns")
                         for name in kernels_in(sub))
    return names


def row_blocks(rows: int, max_block_rows: int) -> tuple[int, int]:
    """``(block_rows, grid)`` for an elementwise pass over a
    ``(rows, 128)`` view.

    Mosaic accepts a block whose row count is a multiple of the dtype's
    sublane tile or the whole dimension. So: the whole array when it is
    no taller than the cap, else the cap with a ``cdiv`` grid whose last
    block is ragged (Pallas pads its reads and drops its out-of-bounds
    writes, which an elementwise kernel never notices). No divisor
    search: a divisor of ``rows`` need not be a multiple of 8."""
    if max_block_rows % SUBLANE_ROWS:
        raise ValueError(
            f"block rows {max_block_rows} must be a multiple of "
            f"{SUBLANE_ROWS} (TPU sublane tiling)")
    block = min(rows, max_block_rows)
    return block, pl.cdiv(rows, block)


def shard_over_batch(fn, batched, replicated=()):
    """``fn(*batched, *replicated)`` for a kernel that treats the rows of
    its ``batched`` arguments' leading axis independently.

    XLA cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so a data-parallel step jitted over the
    global mesh with the batch sharded — ``training.make_train_step`` on
    more than one chip — does not compile with a bare ``pallas_call`` in
    the model. On a multi-device world, outside any ``shard_map`` of the
    caller's own, the call is therefore wrapped in a ``shard_map`` over
    the global axes: each device runs the kernel on its rows. One device,
    a caller already inside ``shard_map``, or a batch the world does not
    divide: the plain call, which compiles or raises."""
    from horovod_tpu.core import mesh as mesh_mod
    from horovod_tpu.core import state as state_mod
    from horovod_tpu.parallel.dp import _bound_axes

    st = state_mod.global_state()
    if (not st.initialized or st.mesh.size == 1 or _bound_axes()
            or batched[0].shape[0] % st.mesh.size):
        return fn(*batched, *replicated)
    rows = P(mesh_mod.GLOBAL_AXES)
    return jax.shard_map(
        fn, mesh=st.mesh,
        in_specs=(rows,) * len(batched) + (P(),) * len(replicated),
        out_specs=rows)(*batched, *replicated)

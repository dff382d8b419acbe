"""One process per chip, and one place for compiled programs.

A TPU chip belongs to one process at a time: a parent that has initialised
a jax backend holds it, and a child that needs it then fails or hangs. So
everything that spawns chip-using children (``tpurun``, the subprocess
serving replicas, ``chip_smoke.py --chips 4``) counts the host's chips
WITHOUT jax, hands each child exactly one through the TPU runtime's
visible-chips / per-process-bounds environment, and stays off the backend
itself. The entry scripts share :func:`enable_compile_cache` so their
processes (and every child, by inheritance) find each other's compiled
programs.
"""

from __future__ import annotations

import glob
import os

# The checkout's root (this file is horovod_tpu/utils/chips.py).
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# Git-ignored; fixed, because the directory is part of the cache's key —
# one that moves (a tempfile, a pid, a date) never hits.
COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_compile_cache")


def chips_on_host() -> int:
    """Local chip count, from device nodes (the local_rank domain — the
    analog of MPI_Comm_split_type(SHARED) sizing, mpi_ops.cc:1263-1267).

    Deliberately does NOT touch jax: initialising a TPU backend in a
    launcher would hold the chips and every spawned child would fail with
    "TPU already in use". Finding no node is an error, not one chip: a
    silent 1 would put every child on the first chip.
    """
    override = os.environ.get("HVD_CHIPS_PER_HOST")
    if override:
        return max(1, int(override))
    for pattern in ("/dev/accel*", "/dev/vfio/[0-9]*"):
        n = len(glob.glob(pattern))
        if n:
            return n
    raise RuntimeError(
        "found no TPU device node on this host (/dev/accel*, "
        "/dev/vfio/<n>); run a CPU world instead (tpurun --cpu, "
        "JAX_PLATFORMS=cpu), or set HVD_CHIPS_PER_HOST if the chips are "
        "exposed some other way")


def one_chip_env(index: int) -> dict:
    """Environment that gives a child process chip ``index`` and no other:
    the TPU runtime opens only that chip and treats it as a whole 1x1x1
    topology, so siblings never contend for a chip's lock."""
    return {
        "TPU_VISIBLE_CHIPS": str(index),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def enable_compile_cache() -> str:
    """Point jax's persistent compilation cache somewhere every process of
    this checkout agrees on, and return the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and
    this sets no directory in code. Where it is not, it is set — in the
    ENVIRONMENT, so spawned children inherit it, and before jax reads its
    configuration when called before the first ``import jax`` — to the one
    fixed git-ignored directory inside the checkout.
    """
    import jax
    # A cached executable carries the metadata it was compiled with, and a
    # device trace finds the step's phases by it (the named scopes of
    # docs/timeline.md). jax's default key strips metadata, so a program
    # WITH scopes would be served one compiled WITHOUT them (measured: PR
    # 26, PERF.md section 6). Keep it in the key — in the environment too,
    # for children. The price: a moved checkout or shifted source lines
    # compile again.
    os.environ.setdefault("JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY",
                          "1")
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = COMPILE_CACHE_DIR
    # jax reads the variable when it is imported; cover a caller that
    # imported jax first.
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR

"""Process bootstrap — MUST run before any XLA backend touch: place the
persistent compile cache, then (multi-process only) join the jax runtime.

Compile cache (:func:`configure_compile_cache`): every entry point —
``chip_smoke.py``'s children, ``benchmarks.run``, the examples, the
launcher's children — imports the package first, so they all share one
cache. If
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and the code
sets no directory. Otherwise the cache lives at ONE fixed path inside
the checkout (:data:`CACHE_ROOT`, git-ignored), never a temp dir, pid or
time: a directory that moves never hits. A process pinned to the CPU
(``JAX_PLATFORMS=cpu``: the tests, rehearsals) gets no cache from this
code.

Multi-process:

``jax.distributed.initialize`` has to be called before the first
``jax.devices()``/computation, but importing the framework already
touches the backend (op registration, dtype tables). So the very first
statement of ``paddle_tpu/__init__`` calls :func:`bootstrap`, which joins
the global jax runtime when the launcher envs say this is a ranked
process of a pod (reference analog: parallel.py:943 init_parallel_env's
store+ProcessGroup bootstrap, which Paddle likewise triggers before any
collective).

Kept dependency-free (no other paddle_tpu imports) so it can run first.
The jax coordination service address is ``PADDLE_MASTER`` host with
port+1 — the TCPStore owns the master port itself — or the explicit
``JAX_COORDINATOR_ADDRESS`` override set by the launcher.
"""
from __future__ import annotations

import os
from typing import Optional

_done = False

# <checkout>/.paddle_tpu_cache — beside the package, inside the checkout.
# The autotune cache (ops/pallas/autotune.py) lives here too.
CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".paddle_tpu_cache")


def compile_cache_dir(environ, platforms: str) -> Optional[str]:
    """The directory this code should point JAX's cache at, or None to
    set nothing: the variable placed it from outside, or the process is
    pinned to the CPU (XLA:CPU's AOT loader logs a machine-feature
    error on every hit, and the CPU only ever compiles toy sizes)."""
    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    if platforms.strip().lower() == "cpu":
        return None
    return os.path.join(CACHE_ROOT, "xla")


def configure_compile_cache() -> Optional[str]:
    """Place JAX's persistent compilation cache (see the module
    docstring); returns the directory set here, or None.

    The minimum-compile-time threshold drops from JAX's 1 s to 0: the
    serving loop compiles dozens of small programs (one per bucket of
    its lattice) and a warm start should not recompile the ones that
    each took under a second."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = compile_cache_dir(os.environ, jax.config.jax_platforms or "")
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def bootstrap() -> None:
    global _done
    if _done:
        return
    _done = True
    configure_compile_cache()
    world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    if world <= 1:
        return
    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    coord = os.environ.get("JAX_COORDINATOR_ADDRESS")
    if not coord:
        master = os.environ.get("PADDLE_MASTER", "")
        if not master:
            return  # no rendezvous info — stay single-process
        host, _, port = master.partition(":")
        coord = f"{host or '127.0.0.1'}:{int(port or 0) + 1}"
    import jax

    # XLA:CPU cross-process collectives ride gloo (the reference's
    # process_group_gloo.cc role); harmless on TPU backends.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=world, process_id=rank)

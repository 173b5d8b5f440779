"""Run one benchmark cell on the machine's accelerator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Prints a few lines about the window, then, as its last line, one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and with ``--trace 1`` ``breakdown``), and last ``checks``, each
compared number beside its limit.  Exits non-zero, printing no result,
without a TPU, with fewer chips than the cell asks for, or without the
program's ``src/`` beside ``bench/``.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def configure() -> bool:
    """Put the program and the benchmark on the path and JAX's compile
    cache at its fixed place inside the checkout (the program's
    ``place_compile_cache`` takes it from the environment).  False when
    the program is not beside the benchmark."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import repro.serving  # noqa: F401
    except ImportError as e:
        print(f"bench: the program's src/ is not beside bench/ ({e})",
              file=sys.stderr)
        return False
    import jax

    from repro.launch.compile_cache import place_compile_cache
    # cache every program, so that only a cell's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    place_compile_cache()
    return True


def main() -> int:
    if not configure():
        return 2
    from bench.harness import main as run
    return run(t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())

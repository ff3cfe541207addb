"""Run one benchmark cell on the chip and print its result.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Refuses to measure anywhere but on a TPU with as many chips as the cell asks
for: it then exits non-zero and prints no result.  JAX's persistent
compilation cache is kept where ``JAX_COMPILATION_CACHE_DIR`` says, or else in
``.jax_cache`` at the root of the checkout.  The lines before the last on
standard output give the device, the set-up's parts (init, data, warm query;
trace, lower and compile seconds; cache hits), every query of the window and
the comparison with the reference.  The last line is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number compared with
the reference beside its limit, which also end standard error.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(prog="chipbench.run", description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def configure_cache(jax) -> None:
    """Persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else ``.jax_cache`` at the root of the checkout; every program is kept."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(jax, chips: int) -> None:
    """Exit (non-zero, no result) unless JAX runs on a TPU with enough chips."""
    platform = jax.default_backend()
    if platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU; JAX's backend is {platform!r}")
    if len(jax.devices()) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX sees "
                         f"{len(jax.devices())}")


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import repro  # noqa: F401  (the program under test: without it, nothing to run)

    from chipbench import harness
    from chipbench.compiles import CompileCounter

    cell = harness.load_cell(args.workload, ROOT)
    import jax

    require_chips(jax, cell.chips)
    configure_cache(jax)
    compiles = CompileCounter(jax)
    line = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), jax=jax,
                            compiles=compiles, process_start=PROCESS_START,
                            log=lambda s: print(s, flush=True))
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

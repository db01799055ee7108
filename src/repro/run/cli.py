"""``python -m repro run`` — the one CLI in front of every mode.

    python -m repro run --arch gemma-7b --mode train \
        --set trainer.total_steps=50 --set model.param_sharding=wus
    python -m repro run --spec runs/gemma_7b_tp2d.json --set serve.max_batch=8
    python -m repro run --mode bench --set bench.smoke=true

Resolution order (later wins): spec file -> dedicated flags
(--arch/--mode/--mesh/--scenario/--seed/--reduced|--full) -> --set
assignments. The legacy launchers (``repro.launch.train|serve|dryrun``,
``repro.bench.run``) are thin shims that build the same RunSpec from
their historical flags and call the same dispatcher.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
from pathlib import Path

from repro.run.overrides import SpecError, apply_assignments
from repro.run.spec import MESHES, MODES, SCENARIOS, RunSpec
from repro.run.specfile import load_spec_file

_USAGE = "usage: python -m repro run [--spec F] [--arch A] [--mode M] ..."
# Persistent compile cache when JAX_COMPILATION_CACHE_DIR is unset: one
# fixed, git-ignored path in the checkout (the path is part of the key,
# so a directory that moves never hits).
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is set here; otherwise the cache goes to
    :data:`DEFAULT_COMPILE_CACHE`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)


def build_spec(args) -> RunSpec:
    spec = load_spec_file(args.spec) if args.spec else RunSpec()
    flags = {
        name: getattr(args, name)
        for name in ("arch", "mode", "mesh", "scenario", "seed", "reduced")
        if getattr(args, name) is not None
    }
    if flags:
        spec = dataclasses.replace(spec, **flags)
    if getattr(args, "metrics_out", None):
        spec = dataclasses.replace(
            spec, trainer=dataclasses.replace(
                spec.trainer, metrics_out=args.metrics_out))
    return apply_assignments(spec, args.set or [])


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] != "run":
        print(f"{_USAGE}\nunknown command "
              f"{argv[0] if argv else '(none)'!r}; commands: run",
              file=sys.stderr)
        return 2

    ap = argparse.ArgumentParser(prog="repro run", description=__doc__)
    ap.add_argument("--spec", default=None,
                    help="JSON/TOML run-spec file (runs/*.json)")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--mode", default=None, choices=MODES)
    ap.add_argument("--mesh", default=None, choices=MESHES)
    ap.add_argument("--scenario", default=None,
                    choices=list(SCENARIOS[1:]))
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--reduced", dest="reduced", action="store_true",
                    default=None, help="smoke-scale config (the default)")
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="published dimensions (pod-scale)")
    ap.add_argument("--metrics-out", default=None, metavar="FILE",
                    help="stream every fit record to FILE as JSONL "
                         "(shorthand for --set trainer.metrics_out=FILE)")
    ap.add_argument("--set", action="append", metavar="KEY=VALUE",
                    help="dotted-key override, e.g. trainer.total_steps=50")
    args = ap.parse_args(argv[1:])

    try:
        spec = build_spec(args)
    except SpecError as e:
        print(f"spec error: {e}", file=sys.stderr)
        return 2

    if spec.mode == "dryrun":
        # jax locks the device count at first init; the dry-run needs its
        # placeholder CPU devices (same flag repro.launch.dryrun sets —
        # one shared contract, see repro.launch.dryrun_xla_flags).
        from repro.launch import dryrun_xla_flags

        os.environ["XLA_FLAGS"] = dryrun_xla_flags()

    use_compile_cache()
    from repro.run.dispatch import run_spec

    # run_spec stores the structured result in dispatch.LAST_RESULT for
    # in-process callers (tests, notebooks) driving the CLI.
    return int(run_spec(spec).get("exit_code", 0))


if __name__ == "__main__":
    sys.exit(main())

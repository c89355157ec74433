"""``python -m repro.analysis audit`` — the contract audit CLI.

Sweeps the full binding matrix through the contract passes (tracing
only, zero solver executions), prints the human-readable contract
table, writes ``experiments/contract_audit.json``, and exits non-zero
when any cell deviates from the paper-expected outcome matrix.  This is
the CI ``analysis-audit`` job.

The cell list is derived from the scenario registry
(:mod:`repro.scenarios`): every registered scenario contributes one
contract row on top of the dense acceptance matrix, and ``--scenarios
FILE`` registers extra scenario dicts for this run.  Scenario problems
— an unregistered operator class, an unknown preconditioner — exit
with a one-line message (exit code 2), never a traceback.
"""
import argparse
import json
import os
import sys


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python -m repro.analysis")
    sub = ap.add_subparsers(dest="cmd", required=True)
    audit_p = sub.add_parser(
        "audit", help="statically verify the contract matrix")
    audit_p.add_argument("--quick", action="store_true",
                         help="core matrix only (CI mode): skip the "
                         "extra kernel-dispatching preconditioner cells")
    audit_p.add_argument("--out", default="experiments/contract_audit.json",
                         help="artifact path (default: %(default)s)")
    audit_p.add_argument("--no-mesh", action="store_true",
                         help="skip the sharded mesh smoke cells")
    audit_p.add_argument("--devices", type=int, default=8,
                         help="fake host devices for the mesh smoke "
                         "(default: %(default)s; set BEFORE jax imports)")
    audit_p.add_argument("--scenarios", default=None, metavar="FILE",
                         help="JSON file with extra scenario dicts to "
                         "register before the audit (each becomes one "
                         "contract row)")
    args = ap.parse_args(argv)

    # The mesh smoke needs the fake CPU devices staged before any XLA
    # backend initializes.  Decide from the environment alone — never by
    # asking the backend for its devices, which would take an
    # accelerator this process does not need — and re-exec once on the
    # CPU with the flag in place.
    if args.devices > 1 and not args.no_mesh:
        flag = f"--xla_force_host_platform_device_count={args.devices}"
        flags = os.environ.get("XLA_FLAGS", "")
        if flag not in flags.split():
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       XLA_FLAGS=f"{flags} {flag}".strip())
            os.execve(sys.executable,
                      [sys.executable, "-m", "repro.analysis"]
                      + list(argv if argv is not None else sys.argv[1:]),
                      env)
    import jax
    jax.config.update("jax_enable_x64", True)

    from repro.analysis.audit import audit_table, run_audit
    from repro.scenarios import ScenarioError

    try:
        if args.scenarios:
            from repro.scenarios.__main__ import _register_file
            _register_file(args.scenarios)
        artifact = run_audit(quick=args.quick,
                             mesh_smoke=not args.no_mesh)
    except ScenarioError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    out = args.out
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump(artifact, f, indent=1, sort_keys=True)
            f.write("\n")
    print(audit_table(artifact))
    if out:
        print(f"\nartifact: {out}")
    return 0 if artifact["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

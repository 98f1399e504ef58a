"""Diff the COMMITTED root-level BENCH_*.json artifacts against a fresh
benchmark run.

``benchmarks/run.py`` mirrors every fresh ``results/bench/BENCH_*.json``
to the repo root so the perf trajectory is committed and reviewable
across PRs (results/ itself is gitignored).  This checker keeps those
tracked copies honest.  Crucially, the baseline is read from **git HEAD**
(``git show HEAD:<name>``), NOT from the working-tree root file — the
bench run that just executed has already overwritten the working-tree
copy with the fresh artifact, so comparing the file on disk would be a
tautology.  For every requested artifact the checker asserts that

  * a committed copy exists at HEAD (the trajectory is actually
    recorded),
  * the fresh counterpart from this run exists in results/bench/, and
  * every CONTRACT field present in the committed copy matches the fresh
    run bit-for-bit.

Contract fields are the run-invariant claims — bit-identity, zero fused
hop bytes, the int8 resident-byte reduction, adds-vs-density scaling,
single-launch streaming, device counts — never wall-clock timings, which
legitimately drift between runners.  A contract mismatch means a kernel
or accounting regression (or a stale committed artifact: re-run the
suite and commit the refreshed root copies).

  PYTHONPATH=src python -m benchmarks.check_tracked \\
      BENCH_fused.json BENCH_fused_multilayer.json
  PYTHONPATH=src python -m benchmarks.check_tracked --all

``--all`` (or no arguments) checks **every** BENCH_*.json committed at
HEAD — discovered with ``git ls-tree``, not hand-listed.  This closes
the hole where a newly committed artifact whose producing suite silently
stopped running would never be diffed: an explicit CI list only checks
what someone remembered to add, the glob checks what the repo actually
claims.  A committed artifact with no fresh results/bench counterpart is
a failure, not a skip.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(REPO_ROOT, "results", "bench")

# Dotted paths compared when present in the tracked copy.
CONTRACT_FIELDS = [
    "bit_identical",
    "hop_bytes.fused",
    "hop_bytes.fused_total",
    "fused_single_launch",
    "resident_weight_bytes.reduction",
    "sparse.scaling_ok",
    "single_launch",
    "explicit_fused_raises",
    "devices",
    # telemetry / adaptive-controller contract (BENCH_telemetry.json)
    "telemetry_bit_identical",
    "adds_match",
    "adaptive_matches_frozen",
    # serving-tier contract (BENCH_router.json)
    "tier_bit_identical",
    "shed_accounting_ok",
    "rollout_preserves_inflight",
    "rollout_completed",
    # fault-tolerance contract (BENCH_faults.json)
    "evacuation_bit_identical",
    "ladder_bit_identical",
    "ladder_repromoted",
    "replay_deterministic",
    "no_silent_loss",
    "process_failover_bit_identical",
    "ledger_survives_coordinator_restart",
    "process_replay_deterministic",
    # model-axis sharding contract (BENCH_model_sharded.json)
    "model_sharded_bit_identical",
    "telemetry_bit_identical_model",
    "wide_fused_resident",
    "wide_shard_fits_vmem",
    "failover_bit_identical",
    "mesh_shape",
    # autotuner / dispatch-cache contract (BENCH_autotune.json) — the
    # tuned wall-clock itself is provenance, never compared
    "tuned_bit_identical",
    "tuned_not_slower",
    "cache_roundtrip_ok",
]


def _get(obj, dotted):
    for part in dotted.split("."):
        if not isinstance(obj, dict) or part not in obj:
            return None, False
        obj = obj[part]
    return obj, True


def _committed_json(name: str, repo_root: str = REPO_ROOT):
    """The artifact as committed at git HEAD, or None with a reason.

    The working-tree root copy is NOT a usable baseline here: the bench
    run mirrors its fresh output over it before this checker runs.
    """
    try:
        out = subprocess.run(
            ["git", "show", f"HEAD:{name}"], cwd=repo_root,
            capture_output=True, text=True, timeout=60)
    except (OSError, subprocess.TimeoutExpired) as e:
        return None, f"git unavailable ({e})"
    if out.returncode != 0:
        return None, "not committed at HEAD — run the suite and commit " \
                     "the mirrored root artifact"
    try:
        return json.loads(out.stdout), None
    except json.JSONDecodeError as e:
        return None, f"committed copy is not valid JSON ({e})"


def check(names: list[str], repo_root: str = REPO_ROOT) -> list[str]:
    bench_dir = os.path.join(repo_root, "results", "bench")
    errors = []
    for name in names:
        tracked, why = _committed_json(name, repo_root)
        if tracked is None:
            errors.append(f"{name}: {why}")
            continue
        fresh_p = os.path.join(bench_dir, name)
        if not os.path.exists(fresh_p):
            errors.append(f"{name}: no fresh results/bench copy — the "
                          f"producing suite did not run (re-run "
                          f"`python -m benchmarks.run` or drop the stale "
                          f"committed artifact)")
            continue
        try:
            with open(fresh_p) as f:
                fresh = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            errors.append(f"{name}: fresh results/bench copy unreadable "
                          f"({e}) — the producing suite crashed mid-write; "
                          f"re-run it")
            continue
        for field in CONTRACT_FIELDS:
            tv, present = _get(tracked, field)
            fv, fresh_present = _get(fresh, field)
            if not present:
                if fresh_present:
                    # The reverse hole: a contract field the bench now
                    # emits but the committed baseline predates.  Skipping
                    # it silently would let the new claim go untracked
                    # forever — the artifact must be re-committed.
                    errors.append(
                        f"{name}: contract field {field!r} added to the "
                        f"bench but missing from the committed copy — "
                        f"re-run the suite and commit the refreshed root "
                        f"artifact")
                continue
            if not fresh_present:
                errors.append(f"{name}: contract field {field!r} vanished "
                              f"from the fresh run")
            elif tv != fv:
                errors.append(f"{name}: contract field {field!r} tracked="
                              f"{tv!r} fresh={fv!r}")
    return errors


def committed_artifacts(repo_root: str = REPO_ROOT) -> list[str]:
    """Every root-level BENCH_*.json tracked at git HEAD."""
    try:
        out = subprocess.run(
            ["git", "ls-tree", "--name-only", "HEAD"], cwd=repo_root,
            capture_output=True, text=True, timeout=60)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise SystemExit(f"TRACKED-ARTIFACT MISMATCH: git unavailable "
                         f"({e}) — run from a git checkout")
    if out.returncode != 0:
        raise SystemExit(f"TRACKED-ARTIFACT MISMATCH: git ls-tree failed "
                         f"({out.stderr.strip()}) — run from a git "
                         f"checkout with at least one commit")
    return sorted(n for n in out.stdout.splitlines()
                  if n.startswith("BENCH_") and n.endswith(".json"))


def main(argv=None, repo_root: str = REPO_ROOT) -> None:
    names = (argv if argv is not None else sys.argv[1:])
    if not names or names == ["--all"]:
        names = committed_artifacts(repo_root)
        print(f"# checking all {len(names)} BENCH_*.json committed at "
              f"HEAD: {', '.join(names)}")
        if not names:
            print("usage: python -m benchmarks.check_tracked "
                  "[BENCH_x.json ... | --all]  (no artifacts at HEAD)")
            sys.exit(2)
    errors = check(list(names), repo_root)
    for e in errors:
        print(f"TRACKED-ARTIFACT MISMATCH: {e}")
    if errors:
        sys.exit(1)
    print(f"# {len(names)} tracked benchmark artifact(s) match the fresh "
          f"run on all contract fields")


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Fail if a fault-plan JSON file violates the FaultPlan schema.

Chaos schedules ride config, not code: a plan exported via
``DS2_FAULT_PLAN=/path/plan.json`` is parsed at import time deep inside whatever entry point
it lands in — a typo'd kind or an inverted window would otherwise
surface as a crash mid-run, long after the operator walked away. This
lint front-loads that failure. The schema is owned by
``deepspeech_tpu.resilience.faults.validate_plan_dict`` — the same
validator ``FaultPlan.from_dict`` enforces at load time — so tool and
runtime can't drift. That includes the episode-relative trigger rules:
a spec mixing wall-clock (``after_s``/``until_s``) and episode
(``on_event``) triggers is rejected (the two clocks would race);
``arm_for_s`` and ``target="@event"`` require ``on_event``;
``min_load`` must be a number >= 0. The advisory pass additionally
warns when ``on_event`` names a controller event nothing is wired to
emit (``faults.KNOWN_EVENTS``) — the plan loads fine but the spec
would stay un-armed forever — and when a point/kind pairing no call
site acts on would silently no-op: the cross-process transport
points (``transport.send`` / ``transport.recv`` / ``transport.ack``)
accept ``error`` / ``latency`` / ``unavailable`` everywhere, but
``partial_write`` (tearing a wire frame mid-send) is only honored at
``transport.send`` — a plan tearing the receive or ack leg describes
a fault the plane cannot produce. Wired into tier-1 via
tests/test_tools.py.

Usage:
    python tools/check_fault_plan.py plan.json [more.json ...]
    some-generator | python tools/check_fault_plan.py -
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from deepspeech_tpu.resilience.faults import (lint_plan_points,  # noqa: E402
                                              validate_plan_dict)


def scan(text: str) -> List[str]:
    """Problems with one fault-plan document ([] = valid)."""
    try:
        obj = json.loads(text)
    except json.JSONDecodeError as e:
        return [f"invalid JSON: {e}"]
    return validate_plan_dict(obj)


def warnings_for(text: str) -> List[str]:
    """Advisory findings for a schema-valid plan: unknown injection
    points and kinds no call site acts on (the plan loads fine but the
    fault would never fire where intended). Non-failing."""
    try:
        obj = json.loads(text)
    except json.JSONDecodeError:
        return []
    return lint_plan_points(obj)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="lint: fault-plan JSON must satisfy the FaultPlan "
                    "schema (resilience.faults.validate_plan_dict)")
    ap.add_argument("paths", nargs="+",
                    help="fault-plan JSON file(s) to validate "
                         "('-' = stdin)")
    args = ap.parse_args(argv)
    bad = 0
    n_faults = 0
    for path in args.paths:
        if path == "-":
            text = sys.stdin.read()
        else:
            with open(path, errors="replace") as fh:
                text = fh.read()
        problems = scan(text)
        for p in problems:
            bad += 1
            print(f"check_fault_plan: {path}: {p}", file=sys.stderr)
        if not problems:
            n_faults += len(json.loads(text).get("faults", []))
            for w in warnings_for(text):
                print(f"check_fault_plan: {path}: warning: {w}",
                      file=sys.stderr)
    if bad:
        print(f"check_fault_plan: {bad} schema violation(s)",
              file=sys.stderr)
        return 1
    print(f"check_fault_plan: OK ({n_faults} fault(s))")
    return 0


if __name__ == "__main__":
    sys.exit(main())

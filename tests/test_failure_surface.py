"""Failure-surface contracts (tier-1): the RPC exception codec must
round-trip EVERY SentioError subclass with its full wire surface, and
every chaos injection point planted in the package must be armed by at
least one test (an orphaned point is dead chaos coverage).

The static halves of these contracts live in the analyzer
(sentio_tpu/analysis/failures.py, gated by test_lint.py); this file is
the runtime half — a future subclass with an incompatible ``__init__``
fails HERE, not in a chaos drill.
"""

import json
from pathlib import Path

import pytest

from sentio_tpu.infra import exceptions as exc_mod
from sentio_tpu.runtime.worker import _decode_exc, _encode_exc

REPO = Path(__file__).resolve().parents[1]


def _all_subclasses(cls):
    out = []
    for sub in cls.__subclasses__():
        out.append(sub)
        out.extend(_all_subclasses(sub))
    return out


def _taxonomy():
    """Every SentioError subclass the codec promises to round-trip —
    auto-discovered, so a new subclass joins the gate by existing.
    Test-local subclasses (other modules) are exactly the ones the codec
    deliberately degrades; they are covered separately below."""
    return sorted(
        (c for c in _all_subclasses(exc_mod.SentioError)
         if c.__module__ == "sentio_tpu.infra.exceptions"),
        key=lambda c: c.__name__,
    )


class TestCodecExhaustiveness:
    def test_taxonomy_discovered(self):
        names = {c.__name__ for c in _taxonomy()}
        assert {"ServiceOverloaded", "ReplicaUnavailable",
                "DeadlineExceededError", "GraphError"} <= names

    @pytest.mark.parametrize("cls", _taxonomy(), ids=lambda c: c.__name__)
    def test_roundtrip_preserves_wire_surface(self, cls):
        exc = cls(
            "wire probe",
            details={"k": "v", "retry_after_s": 7.25},
            retryable=True,
        )
        wire = _encode_exc(exc)
        json.dumps(wire)  # every frame payload must serialize
        back = _decode_exc(wire)
        assert type(back) is cls
        assert back.message == "wire probe"
        assert back.status == exc.status
        assert back.code == exc.code
        assert back.retryable is True
        assert back.details["k"] == "v"
        assert back.details["retry_after_s"] == 7.25
        assert getattr(back, "soft_fail_exempt", False) == getattr(
            exc, "soft_fail_exempt", False)

    def test_out_of_module_subclass_degrades_not_crashes(self):
        """The seeded codec regression, runtime half: a SentioError
        subclass the decode path cannot resolve by name degrades to a
        RuntimeError naming the original type — a worker bug must not
        masquerade as a retryable typed error, and decode must never
        crash the dispatcher."""

        class RogueError(exc_mod.SentioError):
            def __init__(self, message, slot):
                super().__init__(message)
                self.slot = slot

        wire = _encode_exc(RogueError("boom", 3))
        back = _decode_exc(wire)
        assert type(back) is RuntimeError
        assert "RogueError" in str(back)
        assert "boom" in str(back)


class TestFaultPointCoverage:
    def test_every_fault_point_armed(self):
        from sentio_tpu.analysis.failures import (
            collect_armed_points,
            collect_fault_points,
        )
        from sentio_tpu.analysis.runner import PACKAGE_ROOT, parse_paths

        pkg, errs = parse_paths([PACKAGE_ROOT])
        assert errs == []
        arming, errs = parse_paths([REPO / "tests"])
        assert errs == []
        points = collect_fault_points(pkg)
        armed = collect_armed_points(arming)
        orphans = sorted(set(points) - set(armed))
        assert not orphans, (
            f"fault points never armed by any test (dead "
            f"chaos coverage): {orphans} — planted at "
            f"{[points[o] for o in orphans]}"
        )

    def test_committed_inventory_current(self):
        """analysis/fault_points.json is the committed chaos-coverage
        map; regenerate with
        ``python -m sentio_tpu.analysis.failures > sentio_tpu/analysis/fault_points.json``."""
        from sentio_tpu.analysis.failures import fault_point_inventory

        committed = json.loads(
            (REPO / "sentio_tpu/analysis/fault_points.json").read_text())
        assert committed == fault_point_inventory(), (
            "fault-point inventory drifted — regenerate "
            "sentio_tpu/analysis/fault_points.json"
        )

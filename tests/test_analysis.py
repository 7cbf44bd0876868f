"""Contract tests for the :mod:`repro.analysis` invariant analyzer.

Per rule: one flagged fixture, one clean fixture, one suppressed
fixture (``# tuna: ignore[RULE]``), one baselined run — plus the CLI
exit-code contract, baseline round-trips, TUNA006 schema-evolution
scenarios, and a meta-test that every registered rule has fixtures (a
new rule module cannot land untested). The final test runs the analyzer
over this repo's real tree with the committed baseline: the merge
contract CI gates on.
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from repro.analysis.baseline import (
    PLACEHOLDER_REASON,
    Baseline,
    BaselineError,
    build_updated,
)
from repro.analysis.cli import main as cli_main
from repro.analysis.core import (
    RULES,
    collect_files,
    instantiate_rules,
    run_analysis,
)

REPO_ROOT = Path(__file__).resolve().parent.parent


def write_tree(root: Path, files: dict[str, str]) -> None:
    for rel, content in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(content)


def analyze(root: Path, baseline=None, select=None):
    rels = collect_files(root, ["."])
    return run_analysis(
        root, rels, baseline=baseline or Baseline.empty(), select=select
    )


def codes(findings) -> set:
    return {f.rule for f in findings}


# --------------------------------------------------------------- fixtures
#
# Each rule: {path: file the snippet lands in, flagged / clean /
# suppressed: source text}. ``clean_needs_pin``: the rule reports an
# unpinned contract as a finding, so the clean variant runs against a
# baseline produced by --update-baseline (exactly the documented flow).

RULE_FIXTURES = {
    "TUNA001": {
        "path": "src/repro/sim/workloads/gen.py",
        "flagged": (
            "import numpy as np\n"
            "def trace(n):\n"
            "    return np.random.rand(n)\n"
        ),
        "clean": (
            "import numpy as np\n"
            "def trace(n, seed):\n"
            "    rng = np.random.default_rng(seed)\n"
            "    return rng.random(n)\n"
        ),
        "suppressed": (
            "import numpy as np\n"
            "def trace(n):\n"
            "    return np.random.rand(n)  "
            "# tuna: ignore[TUNA001] fixture: legacy oracle\n"
        ),
    },
    "TUNA002": {
        "path": "src/repro/serving/cache.py",
        "flagged": (
            "def pin(pool, page):\n"
            "    pool.tier[page] = 1\n"
        ),
        "clean": (
            "def pin(pool, page):\n"
            "    if pool.tier[page] == 1:\n"
            "        return\n"
            "    pool.place([page])\n"
        ),
        "suppressed": (
            "def pin(pool, page):\n"
            "    # tuna: ignore[TUNA002] fixture: teaching example\n"
            "    pool.tier[page] = 1\n"
        ),
    },
    "TUNA003": {
        "path": "src/repro/tiering/reference_pool.py",
        # without a pinned digest the frozen contract is unenforced:
        # that is itself the finding
        "flagged": "class ReferencePagePool:\n    pass\n",
        "clean": "class ReferencePagePool:\n    pass\n",
        "clean_needs_pin": True,
        "suppressed": (
            "# tuna: ignore[TUNA003] fixture: fork of the frozen pool\n"
            "class ReferencePagePool:\n    pass\n"
        ),
    },
    "TUNA004": {
        "path": "src/repro/sim/jax_engine.py",
        "flagged": (
            "import jax\n"
            "@jax.jit\n"
            "def step(heat, decay, touch):\n"
            "    return heat * decay + touch\n"
        ),
        "clean": (
            "import jax\n"
            "@jax.jit\n"
            "def step(decayed, touch):\n"
            "    return decayed + touch\n"
        ),
        "suppressed": (
            "import jax\n"
            "@jax.jit\n"
            "def step(heat, decay, touch):\n"
            "    return heat * decay + touch  "
            "# tuna: ignore[TUNA004] fixture: no bit-exact contract\n"
        ),
    },
    "TUNA006": {
        "path": "src/repro/sim/api.py",
        # unpinned schema fingerprint is the finding; pinning it (the
        # --update-baseline flow) is the clean state
        "flagged": (
            'RUNSET_SCHEMA = "tuna-runset-v1"\n'
            "RUNSET_SCHEMA_COMPAT = (RUNSET_SCHEMA,)\n"
            "class RunSet:\n"
            "    def to_json(self):\n"
            '        return {"schema": RUNSET_SCHEMA, "alpha": 1}\n'
        ),
        "clean": (
            'RUNSET_SCHEMA = "tuna-runset-v1"\n'
            "RUNSET_SCHEMA_COMPAT = (RUNSET_SCHEMA,)\n"
            "class RunSet:\n"
            "    def to_json(self):\n"
            '        return {"schema": RUNSET_SCHEMA, "alpha": 1}\n'
        ),
        "clean_needs_pin": True,
        "suppressed": (
            "# tuna: ignore[TUNA006] fixture: schema work in progress\n"
            'RUNSET_SCHEMA = "tuna-runset-v1"\n'
            "RUNSET_SCHEMA_COMPAT = (RUNSET_SCHEMA,)\n"
            "class RunSet:\n"
            "    def to_json(self):\n"
            '        return {"schema": RUNSET_SCHEMA, "alpha": 1}\n'
        ),
    },
    "TUNA007": {
        "path": "src/repro/sim/profile.py",
        "flagged": (
            "import time\n"
            "def stamp():\n"
            "    return time.perf_counter()\n"
        ),
        "clean": (
            "def stamp(interval_costs):\n"
            "    return sum(c.total for c in interval_costs)\n"
        ),
        "suppressed": (
            "import time\n"
            "def stamp():\n"
            "    # tuna: ignore[TUNA007] fixture: debug-only path\n"
            "    return time.perf_counter()\n"
        ),
    },
    "TUNA009": {
        "path": "src/repro/fleet/balancer.py",
        "flagged": (
            "def grant(controllers, sizes):\n"
            "    for ctl, s in zip(controllers, sizes):\n"
            "        ctl.set_size(s)\n"
        ),
        "clean": (
            "def grant(arbiter, sizes):\n"
            "    arbiter.apply(sizes)\n"
        ),
        "suppressed": (
            "def grant(controllers, sizes):\n"
            "    for ctl, s in zip(controllers, sizes):\n"
            "        # tuna: ignore[TUNA009] fixture: teaching example\n"
            "        ctl.set_size(s)\n"
        ),
    },
    "TUNA010": {
        "path": "src/repro/timing/probe.py",
        "flagged": (
            "from repro.sim.engine import _simulate\n"
            "def clock(trace):\n"
            "    return _simulate(trace)\n"
        ),
        "clean": (
            "from repro.sim.costmodel import HardwareProfile\n"
            "def clock(hw: HardwareProfile):\n"
            "    return hw.lat_fast\n"
        ),
        "suppressed": (
            "from repro.sim.engine import _simulate  "
            "# tuna: ignore[TUNA010] fixture: teaching example\n"
        ),
    },
    "TUNA008": {
        "path": "benchmarks/drv.py",
        "flagged": (
            "from repro.sim.api import Scenario\n"
            "s = Scenario(trace=lambda: make_trace())\n"
        ),
        "clean": (
            "from repro.sim.api import Scenario\n"
            's = Scenario(trace="xsbench")\n'
        ),
        "suppressed": (
            "from repro.sim.api import Scenario\n"
            "s = Scenario(trace=lambda: make_trace())  "
            "# tuna: ignore[TUNA008] fixture: serial-only example\n"
        ),
    },
}


class TestRuleFixtures:
    @pytest.mark.parametrize("code", sorted(RULE_FIXTURES))
    def test_flagged(self, code, tmp_path):
        fx = RULE_FIXTURES[code]
        write_tree(tmp_path, {fx["path"]: fx["flagged"]})
        res, _ = analyze(tmp_path, select=[code])
        assert code in codes(res.findings)
        for f in res.findings:
            assert f.path == fx["path"]
            assert f.message

    @pytest.mark.parametrize("code", sorted(RULE_FIXTURES))
    def test_clean(self, code, tmp_path):
        fx = RULE_FIXTURES[code]
        write_tree(tmp_path, {fx["path"]: fx["clean"]})
        baseline = Baseline.empty()
        if fx.get("clean_needs_pin"):
            res, project = analyze(tmp_path, select=[code])
            baseline = build_updated(
                instantiate_rules([code]), project,
                res.findings + res.baselined, None,
            )
        res, _ = analyze(tmp_path, baseline=baseline, select=[code])
        assert res.findings == []

    @pytest.mark.parametrize("code", sorted(RULE_FIXTURES))
    def test_suppressed(self, code, tmp_path):
        fx = RULE_FIXTURES[code]
        write_tree(tmp_path, {fx["path"]: fx["suppressed"]})
        res, _ = analyze(tmp_path, select=[code])
        assert code not in codes(res.findings)
        assert code in codes(res.suppressed)

    @pytest.mark.parametrize("code", sorted(RULE_FIXTURES))
    def test_baselined(self, code, tmp_path):
        """--update-baseline over a flagged tree makes the next run
        clean: plain findings land in the grandfather list, pin-backed
        ones (TUNA003/TUNA006) are resolved by the pin refresh."""
        fx = RULE_FIXTURES[code]
        write_tree(tmp_path, {fx["path"]: fx["flagged"]})
        res, project = analyze(tmp_path, select=[code])
        assert code in codes(res.findings)
        bl = build_updated(
            instantiate_rules([code]), project,
            res.findings + res.baselined, None,
        )
        res2, _ = analyze(tmp_path, baseline=bl, select=[code])
        assert res2.findings == []
        assert res2.stale_baseline == []

    def test_every_registered_rule_has_fixtures(self):
        """Meta-test: a new rule module cannot land without fixtures
        here (and every fixture names a registered rule)."""
        instantiate_rules()  # import-registers the rule modules
        assert set(RULE_FIXTURES) == set(RULES)
        for code, cls in RULES.items():
            assert cls.name, f"{code} has no name"
            assert cls.description, f"{code} has no description"
            fx = RULE_FIXTURES[code]
            assert {"path", "flagged", "clean", "suppressed"} <= set(fx)


class TestRuleEdges:
    def test_tuna001_unseeded_default_rng(self, tmp_path):
        write_tree(
            tmp_path,
            {
                "src/repro/sim/w.py": (
                    "import numpy as np\n"
                    "rng = np.random.default_rng()\n"
                )
            },
        )
        res, _ = analyze(tmp_path, select=["TUNA001"])
        assert len(res.findings) == 1
        assert "no seed" in res.findings[0].message

    def test_tuna001_out_of_scope_dir_not_flagged(self, tmp_path):
        write_tree(
            tmp_path,
            {"benchmarks/b.py": "import numpy as np\nx = np.random.rand(3)\n"},
        )
        res, _ = analyze(tmp_path, select=["TUNA001"])
        assert res.findings == []

    def test_tuna002_pool_classes_exempt(self, tmp_path):
        write_tree(
            tmp_path,
            {
                "src/repro/tiering/page_pool.py": (
                    "class TieredPagePool:\n"
                    "    def place(self, pages):\n"
                    "        self.tier[pages] = 1\n"
                )
            },
        )
        res, _ = analyze(tmp_path, select=["TUNA002"])
        assert res.findings == []

    def test_tuna003_edit_after_pin_is_flagged(self, tmp_path):
        fx = RULE_FIXTURES["TUNA003"]
        write_tree(tmp_path, {fx["path"]: fx["clean"]})
        res, project = analyze(tmp_path, select=["TUNA003"])
        bl = build_updated(
            instantiate_rules(["TUNA003"]), project,
            res.findings, None,
        )
        (tmp_path / fx["path"]).write_text(fx["clean"] + "# drive-by\n")
        res2, _ = analyze(tmp_path, baseline=bl, select=["TUNA003"])
        assert codes(res2.findings) == {"TUNA003"}
        assert "frozen" in res2.findings[0].message

    def test_tuna004_unjitted_function_not_flagged(self, tmp_path):
        write_tree(
            tmp_path,
            {
                "src/repro/sim/jax_engine.py": (
                    "def host_side(a, b, c):\n"
                    "    return a * b + c\n"
                )
            },
        )
        res, _ = analyze(tmp_path, select=["TUNA004"])
        assert res.findings == []

    def test_tuna004_lax_callback_reachable(self, tmp_path):
        """Reachability follows by-name references: a while_loop body
        handed to lax from inside a jitted function is jit code."""
        write_tree(
            tmp_path,
            {
                "src/repro/sim/jax_engine.py": (
                    "import jax\n"
                    "from jax import lax\n"
                    "def body(st):\n"
                    "    a, b, c = st\n"
                    "    return (a * b + c, b, c)\n"
                    "@jax.jit\n"
                    "def step(st):\n"
                    "    return lax.while_loop(lambda s: True, body, st)\n"
                )
            },
        )
        res, _ = analyze(tmp_path, select=["TUNA004"])
        assert codes(res.findings) == {"TUNA004"}

    def test_tuna004_host_effects_under_jit(self, tmp_path):
        write_tree(
            tmp_path,
            {
                "src/repro/kernels/k.py": (
                    "import jax\n"
                    "import time\n"
                    "@jax.jit\n"
                    "def step(x):\n"
                    "    print(x)\n"
                    "    t = time.time()\n"
                    "    return x\n"
                )
            },
        )
        res, _ = analyze(tmp_path, select=["TUNA004"])
        msgs = " ".join(f.message for f in res.findings)
        assert "print()" in msgs and "time.time()" in msgs

    def test_tuna007_launch_exempt(self, tmp_path):
        write_tree(
            tmp_path,
            {
                "src/repro/launch/trainer.py": (
                    "import time\n"
                    "def step():\n"
                    "    return time.time()\n"
                )
            },
        )
        res, _ = analyze(tmp_path, select=["TUNA007"])
        assert res.findings == []

    def test_tuna009_arbiter_exempt(self, tmp_path):
        write_tree(
            tmp_path,
            {
                "src/repro/fleet/arbiter.py": (
                    "class FleetTunaArbiter:\n"
                    "    def apply(self, granted):\n"
                    "        for s, ctl in enumerate(self.controllers):\n"
                    "            ctl.set_size(int(granted[s]))\n"
                )
            },
        )
        res, _ = analyze(tmp_path, select=["TUNA009"])
        assert res.findings == []

    def test_tuna009_budget_pages_store_flagged(self, tmp_path):
        write_tree(
            tmp_path,
            {
                "src/repro/fleet/runner.py": (
                    "def grow(arbiter, extra):\n"
                    "    arbiter.budget_pages += extra\n"
                )
            },
        )
        res, _ = analyze(tmp_path, select=["TUNA009"])
        assert len(res.findings) == 1
        assert "budget_pages" in res.findings[0].message

    def test_tuna009_non_fleet_code_out_of_scope(self, tmp_path):
        write_tree(
            tmp_path,
            {
                "src/repro/core/tuner.py": (
                    "def steer(ctl, size):\n"
                    "    return ctl.set_size(size)\n"
                )
            },
        )
        res, _ = analyze(tmp_path, select=["TUNA009"])
        assert res.findings == []

    def test_multi_code_suppression(self, tmp_path):
        write_tree(
            tmp_path,
            {
                "src/repro/sim/w.py": (
                    "import time\n"
                    "import numpy as np\n"
                    "def f():\n"
                    "    # tuna: ignore[TUNA001, TUNA007] fixture: both\n"
                    "    return np.random.rand(3), time.time()\n"
                )
            },
        )
        res, _ = analyze(tmp_path, select=["TUNA001", "TUNA007"])
        assert res.findings == []
        assert codes(res.suppressed) == {"TUNA001", "TUNA007"}

    def test_parse_error_is_reported(self, tmp_path):
        write_tree(tmp_path, {"src/repro/sim/bad.py": "def broken(:\n"})
        res, _ = analyze(tmp_path, select=["TUNA001"])
        assert codes(res.findings) == {"PARSE"}


class TestSchemaEvolution:
    """TUNA006 scenario matrix around a pinned mini api.py."""

    BASE = RULE_FIXTURES["TUNA006"]["clean"]
    PATH = RULE_FIXTURES["TUNA006"]["path"]

    def _pinned(self, tmp_path, content):
        write_tree(tmp_path, {self.PATH: content})
        res, project = analyze(tmp_path, select=["TUNA006"])
        return build_updated(
            instantiate_rules(["TUNA006"]), project, res.findings, None
        )

    def test_new_field_without_bump_flagged(self, tmp_path):
        bl = self._pinned(tmp_path, self.BASE)
        drifted = self.BASE.replace(
            '"alpha": 1}', '"alpha": 1, "beta": 2}'
        )
        (tmp_path / self.PATH).write_text(drifted)
        res, _ = analyze(tmp_path, baseline=bl, select=["TUNA006"])
        assert len(res.findings) == 1
        assert "without bumping" in res.findings[0].message
        assert "beta" in res.findings[0].message

    def test_bump_dropping_compat_flagged(self, tmp_path):
        bl = self._pinned(tmp_path, self.BASE)
        bumped = self.BASE.replace(
            'RUNSET_SCHEMA = "tuna-runset-v1"',
            'RUNSET_SCHEMA = "tuna-runset-v2"',
        ).replace('"alpha": 1}', '"alpha": 1, "beta": 2}')
        (tmp_path / self.PATH).write_text(bumped)
        res, _ = analyze(tmp_path, baseline=bl, select=["TUNA006"])
        assert len(res.findings) == 1
        assert "left RUNSET_SCHEMA_COMPAT" in res.findings[0].message

    def test_additive_bump_requires_pin_refresh_then_clean(self, tmp_path):
        bl = self._pinned(tmp_path, self.BASE)
        bumped = self.BASE.replace(
            'RUNSET_SCHEMA = "tuna-runset-v1"',
            'RUNSET_SCHEMA = "tuna-runset-v2"',
        ).replace(
            "RUNSET_SCHEMA_COMPAT = (RUNSET_SCHEMA,)",
            'RUNSET_SCHEMA_COMPAT = ("tuna-runset-v1", RUNSET_SCHEMA)',
        ).replace('"alpha": 1}', '"alpha": 1, "beta": 2}')
        (tmp_path / self.PATH).write_text(bumped)
        res, project = analyze(tmp_path, baseline=bl, select=["TUNA006"])
        assert len(res.findings) == 1
        assert "--update-baseline" in res.findings[0].message
        bl2 = build_updated(
            instantiate_rules(["TUNA006"]), project, res.findings, bl
        )
        res2, _ = analyze(tmp_path, baseline=bl2, select=["TUNA006"])
        assert res2.findings == []

    def test_compat_missing_current_version_flagged(self, tmp_path):
        broken = self.BASE.replace(
            "RUNSET_SCHEMA_COMPAT = (RUNSET_SCHEMA,)",
            'RUNSET_SCHEMA_COMPAT = ("tuna-runset-v0",)',
        )
        bl = self._pinned(tmp_path, self.BASE)
        (tmp_path / self.PATH).write_text(broken)
        res, _ = analyze(tmp_path, baseline=bl, select=["TUNA006"])
        assert any(
            "does not accept the current" in f.message for f in res.findings
        )


class TestBaselineFile:
    def test_round_trip_preserves_reasons(self, tmp_path):
        fx = RULE_FIXTURES["TUNA007"]
        write_tree(tmp_path, {fx["path"]: fx["flagged"]})
        res, project = analyze(tmp_path, select=["TUNA007"])
        bl = build_updated(
            instantiate_rules(["TUNA007"]), project, res.findings, None
        )
        assert bl.findings[0]["reason"] == PLACEHOLDER_REASON
        bl.findings[0]["reason"] = "debug-only code path, removed in PR 9"
        path = tmp_path / "analysis-baseline.json"
        Baseline(bl.findings, bl.pins).save(path)
        loaded = Baseline.load(path)
        res2, project2 = analyze(tmp_path, baseline=loaded, select=["TUNA007"])
        assert res2.findings == [] and len(res2.baselined) == 1
        # a second --update-baseline keeps the human-written reason
        bl2 = build_updated(
            instantiate_rules(["TUNA007"]), project2,
            res2.findings + res2.baselined, loaded,
        )
        assert bl2.findings[0]["reason"] == (
            "debug-only code path, removed in PR 9"
        )

    def test_missing_reason_rejected(self, tmp_path):
        path = tmp_path / "bl.json"
        path.write_text(
            json.dumps(
                {
                    "version": 1,
                    "pins": {},
                    "findings": [
                        {"rule": "TUNA007", "path": "x.py",
                         "fingerprint": "ab", "reason": "  "}
                    ],
                }
            )
        )
        with pytest.raises(BaselineError, match="reason"):
            Baseline.load(path)

    def test_wrong_version_rejected(self, tmp_path):
        path = tmp_path / "bl.json"
        path.write_text(json.dumps({"version": 99, "findings": []}))
        with pytest.raises(BaselineError, match="version"):
            Baseline.load(path)

    def test_fingerprint_survives_line_moves(self, tmp_path):
        fx = RULE_FIXTURES["TUNA007"]
        write_tree(tmp_path, {fx["path"]: fx["flagged"]})
        res, project = analyze(tmp_path, select=["TUNA007"])
        bl = build_updated(
            instantiate_rules(["TUNA007"]), project, res.findings, None
        )
        moved = "# a new leading comment\n\n" + fx["flagged"]
        (tmp_path / fx["path"]).write_text(moved)
        res2, _ = analyze(tmp_path, baseline=bl, select=["TUNA007"])
        assert res2.findings == [] and len(res2.baselined) == 1


class TestCliContract:
    """Exit codes are a contract: 0 clean, 1 findings/stale-under-gate,
    2 usage errors."""

    def _fx(self, tmp_path, variant, code="TUNA007"):
        fx = RULE_FIXTURES[code]
        write_tree(tmp_path, {fx["path"]: fx[variant]})
        return tmp_path

    def test_clean_exits_zero(self, tmp_path, capsys):
        root = self._fx(tmp_path, "clean")
        assert cli_main(["--root", str(root), "src"]) == 0
        assert "0 finding(s)" in capsys.readouterr().out

    def test_findings_exit_one(self, tmp_path, capsys):
        root = self._fx(tmp_path, "flagged")
        assert cli_main(["--root", str(root), "src"]) == 1
        assert "TUNA007" in capsys.readouterr().out

    def test_unknown_select_exits_two(self, tmp_path, capsys):
        root = self._fx(tmp_path, "clean")
        rc = cli_main(["--root", str(root), "--select", "TUNA999", "src"])
        assert rc == 2
        assert "TUNA999" in capsys.readouterr().err

    def test_missing_path_exits_two(self, tmp_path, capsys):
        rc = cli_main(["--root", str(tmp_path), "no_such_dir"])
        assert rc == 2

    def test_malformed_baseline_exits_two(self, tmp_path, capsys):
        root = self._fx(tmp_path, "clean")
        (root / "analysis-baseline.json").write_text("{not json")
        assert cli_main(["--root", str(root), "src"]) == 2

    def test_json_report_and_out_artifact(self, tmp_path, capsys):
        root = self._fx(tmp_path, "flagged")
        rc = cli_main(
            ["--root", str(root), "--format", "json",
             "--out", "report.json", "src"]
        )
        assert rc == 1
        printed = json.loads(capsys.readouterr().out)
        on_disk = json.loads((root / "report.json").read_text())
        assert printed == on_disk
        assert on_disk["exit_code"] == 1
        assert on_disk["findings"][0]["rule"] == "TUNA007"
        assert on_disk["findings"][0]["fingerprint"]

    def test_update_baseline_then_clean_then_stale_gates(
        self, tmp_path, capsys
    ):
        root = self._fx(tmp_path, "flagged")
        assert cli_main(["--root", str(root), "--update-baseline", "src"]) == 0
        assert (root / "analysis-baseline.json").exists()
        # grandfathered: gate passes
        assert cli_main(["--root", str(root), "--gate", "src"]) == 0
        # fix the finding: the entry goes stale; --gate fails, plain
        # run only warns
        fx = RULE_FIXTURES["TUNA007"]
        (root / fx["path"]).write_text(fx["clean"])
        capsys.readouterr()
        assert cli_main(["--root", str(root), "src"]) == 0
        assert "stale baseline entry" in capsys.readouterr().out
        assert cli_main(["--root", str(root), "--gate", "src"]) == 1

    def test_list_rules_names_all(self, tmp_path, capsys):
        assert cli_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for code in RULES:
            assert code in out

    def test_suppressed_and_baselined_do_not_fail(self, tmp_path):
        root = self._fx(tmp_path, "suppressed")
        assert cli_main(["--root", str(root), "--gate", "src"]) == 0


class TestMergedTreeContract:
    def test_repo_tree_is_clean_under_gate(self):
        """The acceptance contract: the analyzer exits 0 over the real
        src/tests/benchmarks with the committed baseline, with every
        registered rule active."""
        instantiate_rules()
        assert len(RULES) >= 7
        rc = cli_main(
            ["--root", str(REPO_ROOT), "--gate", "src", "tests", "benchmarks"]
        )
        assert rc == 0

    def test_console_module_invocation(self, tmp_path):
        """python -m repro.analysis works end to end (the CI job's
        invocation), including --out report writing."""
        import os
        import subprocess
        import sys

        fx = RULE_FIXTURES["TUNA002"]
        write_tree(tmp_path, {fx["path"]: fx["flagged"]})
        env = dict(os.environ)
        env["PYTHONPATH"] = (
            str(REPO_ROOT / "src") + os.pathsep + env.get("PYTHONPATH", "")
        )
        proc = subprocess.run(
            [sys.executable, "-m", "repro.analysis", "--root", str(tmp_path),
             "--out", "report.json", "src"],
            capture_output=True, text=True, env=env, timeout=120,
        )
        assert proc.returncode == 1, proc.stderr
        assert "TUNA002" in proc.stdout
        assert json.loads((tmp_path / "report.json").read_text())["findings"]

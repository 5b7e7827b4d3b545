"""Structured benchmark rows and the perf regression gate.

The satellite fixes: section modules yield typed ``Row`` records (CSV is a
rendering, ``--json`` records real values), malformed subprocess output is a
loud error instead of a silently mangled row, and ``scripts/perf_check.py``
gates a fresh JSON against a committed baseline.
"""
import importlib.util
import json
import os

import pytest

from benchmarks.common import Row, bw_fields, env_metadata, row

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_perf_check():
    path = os.path.join(_ROOT, "scripts", "perf_check.py")
    spec = importlib.util.spec_from_file_location("perf_check", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestRow:
    def test_render_parse_round_trip(self):
        r = row("fig15/flims_sort/n2^12", 123.456, Melem_s=33.17,
                gbps=0.27, n=4096, overflow=False, path="sorted")
        back = Row.parse(r.render())
        assert back.name == r.name
        assert back.us == pytest.approx(r.us, abs=0.1)
        assert back.derived["n"] == 4096
        assert back.derived["overflow"] is False
        assert back.derived["path"] == "sorted"
        assert back.derived["Melem_s"] == pytest.approx(33.17)

    def test_parse_rejects_malformed_line(self):
        with pytest.raises(ValueError, match="malformed benchmark row"):
            Row.parse("just some stray print")
        with pytest.raises(ValueError, match="not a number"):
            Row.parse("name,abc,k=v")
        with pytest.raises(ValueError, match="want k=v"):
            Row.parse("name,1.0,novalue")

    def test_to_record(self):
        rec = row("a/b", 5.0, k=1).to_record("Section X")
        assert rec == {"section": "Section X", "name": "a/b",
                       "us_per_call": 5.0, "derived": {"k": 1}}
        json.dumps(rec)                      # JSON-clean

    def test_bw_fields_roofline_columns(self, monkeypatch):
        from repro.launch import roofline
        # 40 MB in 1 ms -> 40 GB/s, against the v5e's published 819 GB/s
        monkeypatch.setattr(roofline, "device_kind", lambda: "TPU v5 lite")
        f = bw_fields(40_000_000, 1000.0)
        assert f["gbps"] == pytest.approx(40.0)
        assert f["roof_gbps"] == pytest.approx(819.0)
        assert f["roof_frac"] == pytest.approx(f["gbps"] / f["roof_gbps"],
                                               abs=1e-3)
        # a device without published peaks (a CPU) gets no roofline
        monkeypatch.setattr(roofline, "device_kind", lambda: "cpu")
        assert bw_fields(40_000_000, 1000.0) == {"gbps": 40.0}

    def test_env_metadata_fields(self):
        meta = env_metadata("2026-01-01T00:00:00")
        for key in ("backend", "device_count", "device_kind", "jax_version",
                    "git_sha", "timestamp"):
            assert key in meta
        assert meta["device_count"] >= 1
        json.dumps(meta)


class TestCollectRejectsUntypedSections:
    def test_non_row_yield_is_a_hard_error(self):
        import io
        from benchmarks.run import collect

        class BadSection:
            __name__ = "bad_section"

            @staticmethod
            def run():
                return ["name,1.0,free-form string"]
        bad = BadSection()
        bad.__name__ = "bad_section"
        with pytest.raises(TypeError, match="bad_section"):
            collect([(bad, "Bad")], out=io.StringIO())

    def test_rows_render_and_record(self):
        import io
        from benchmarks.run import collect

        class Good:
            __name__ = "good_section"

            @staticmethod
            def run():
                return [row("x/y", 10.0, k=2)]
        good = Good()
        good.__name__ = "good_section"
        buf = io.StringIO()
        records = collect([(good, "Good")], out=buf)
        assert "x/y,10.0,k=2" in buf.getvalue()
        assert records == [{"section": "Good", "name": "x/y",
                            "us_per_call": 10.0, "derived": {"k": 2}}]


class TestPerfCheck:
    def _rows(self, **us_by_name):
        return {("S", k): {"section": "S", "name": k, "us_per_call": v}
                for k, v in us_by_name.items()}

    def test_no_regression(self):
        pc = _load_perf_check()
        regs, imps, _ = pc.compare(self._rows(a=100.0, b=200.0),
                                   self._rows(a=105.0, b=190.0))
        assert regs == [] and imps == []

    def test_regression_detected(self):
        pc = _load_perf_check()
        regs, _, _ = pc.compare(self._rows(a=100.0),
                                self._rows(a=140.0), threshold=0.15)
        assert len(regs) == 1 and "a" in regs[0]

    def test_min_us_noise_floor(self):
        pc = _load_perf_check()
        regs, _, skipped = pc.compare(self._rows(tiny=5.0),
                                      self._rows(tiny=50.0), min_us=100.0)
        assert regs == [] and len(skipped) == 1

    def test_improvement_reported(self):
        pc = _load_perf_check()
        _, imps, _ = pc.compare(self._rows(a=200.0), self._rows(a=100.0))
        assert len(imps) == 1

    def test_main_exit_codes(self, tmp_path):
        pc = _load_perf_check()
        base = {"meta": {}, "rows": [{"section": "S", "name": "a",
                                     "us_per_call": 100.0}]}
        fresh_ok = {"meta": {}, "rows": [{"section": "S", "name": "a",
                                          "us_per_call": 101.0}]}
        fresh_bad = {"meta": {}, "rows": [{"section": "S", "name": "a",
                                           "us_per_call": 400.0}]}
        b = tmp_path / "base.json"
        b.write_text(json.dumps(base))
        ok = tmp_path / "ok.json"
        ok.write_text(json.dumps(fresh_ok))
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps(fresh_bad))
        empty = tmp_path / "empty.json"
        empty.write_text(json.dumps({"meta": {}, "rows": []}))
        assert pc.main([str(b), str(ok)]) == 0
        assert pc.main([str(b), str(bad)]) == 1
        assert pc.main([str(b), str(empty)]) == 2            # missing rows
        assert pc.main([str(b), str(empty), "--allow-missing"]) == 0

    def test_committed_baseline_is_loadable(self):
        pc = _load_perf_check()
        path = os.path.join(_ROOT, "benchmarks", "baselines", "smoke.json")
        rows = pc.load_rows(path)
        assert rows, "committed smoke baseline is empty"
        assert all("us_per_call" in r for r in rows.values())

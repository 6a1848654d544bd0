"""The MLA + MoE reference agrees with the program at smoke widths on the
CPU: a whole run of a cell (8 experts top-2, 2 shared, a leading dense
layer), its prefill and decode through the paged cache compared with
``bench/models/deepseek_v2.py``."""
import time

from bench import harness, spec, tables

ROOT = spec.BENCH / "tests" / "data" / "smoke"
BENCH = {"end_to_end": [{"name": "tokens_per_s"}], "per_layer": []}


def test_deepseek_smoke_run_is_correct(tmp_path, monkeypatch):
    monkeypatch.setattr(tables, "CACHE", tmp_path)
    out = harness.run("deepseek.tiny", 2**33 + 3, 0.4, False,
                      t_start=time.perf_counter(), root=ROOT,
                      require_tpu=False, bench_json=BENCH)
    r = out["result"]
    assert r["correct"] is True, r["checks"]
    assert len(out["picked"]) >= 2
    dispatch = dict(out["checks"][0][1])
    assert set(dispatch) == {"fused", "grouped_fused"}

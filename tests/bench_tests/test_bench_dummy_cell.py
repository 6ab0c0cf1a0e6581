"""A configuration, a traffic mix and a per-layer metric are each added as
new files plus new entries: no file that is there is edited. Shown by
adding a dummy of each in a directory of its own and running it."""
import io
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import manifest as mf  # noqa: E402
from benchmark import run as harness  # noqa: E402


def test_the_harness_takes_a_new_configuration_mix_and_metric(tmp_path):
    bench = tmp_path / "benchmark"
    for sub in ("configs", "traffic", "metrics", "limits"):
        (bench / sub).mkdir(parents=True)
    shutil.copy(os.path.join(mf.HERE, "rehearsal.json"), bench)
    # a new configuration: its file of sizes
    cfg = mf._json(os.path.join(mf.HERE, "configs", "gpt2-345m.json"))
    cfg["n_layer"] = 3
    (bench / "configs" / "dummy-model.json").write_text(json.dumps(cfg))
    # a new traffic mix: a data file of parameters
    (bench / "traffic" / "dummy-mix.json").write_text(json.dumps(
        {"kind": "train", "batch": 6, "seq": 32, "labels": "next"}))
    # a new per-layer metric: a small reader of its own
    (bench / "metrics" / "dummy.steps.py").write_text(
        "def read(run):\n    return float(run['counters']['steps'])\n")
    manifest = {
        "command": ["python3", "benchmark/run.py"], "paths": ["benchmark"],
        "run_seconds": 40,
        "configs": [{"name": "dummy-model", "source": "https://example.org",
                     "file": "benchmark/configs/dummy-model.json",
                     "reduced": ["n_layer"], "why": "a dummy"}],
        "workloads": [{"name": "dummy-model.dummy-mix",
                       "config": "dummy-model", "traffic": "dummy-mix",
                       "chips": 1, "why": "a dummy"}],
        "end_to_end": [
            {"name": "train_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.01, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.1, "source": "host_clock"}],
        "per_layer": [{"name": "dummy.steps", "unit": "count",
                       "better": "higher", "source": "program_counter",
                       "layer": "dummy", "moves": "train_tokens_per_s"}]}
    assert mf.check(manifest, root=str(tmp_path), bench_dir=str(bench)) == []

    cell = mf.Cell(manifest, "dummy-model.dummy-mix", root=str(tmp_path),
                   bench_dir=str(bench))
    assert [m["name"] for m in cell.per_layer] == ["dummy.steps"]
    read = mf.reader("dummy.steps", str(bench))
    assert read({"counters": {"steps": 5}}) == 5.0

    # the rehearsal keeps the mix's own batch out of its tiny sizes only
    # where rehearsal.json names a key: the dummy's labels rule survives
    out = io.StringIO()
    rc = harness.run_cell(cell, 11, 1.0, False, rehearse=True, out=out)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    assert line["attempted"] >= 1

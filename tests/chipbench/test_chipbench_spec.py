"""``BENCHMARK.json`` and the files it names: names, units, keys and the
links between cells, configurations, traffic, limits and metric readers.
Also: the harness refuses to run off a TPU, and a new cell is found by
name from new files alone."""

import json
import os
import re
import shutil
import subprocess
import sys
import textwrap


from chipbench_paths import BENCH_DIR, ROOT

import bench

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
ONE_LINE = re.compile(r"^[^\t\n]{1,200}$")
WIDTHS = re.compile(r"^(hidden_size|.*intermediate_size|.*latent.*|.*state.*|.*proj.*"
                    r"|.*_dim|.*_rank|.*expan.*|num_experts_per_tok)$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "benchmarks/chip/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51 and isinstance(SPEC["run_seconds"], int)
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (ROOT / p).is_dir()
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_lines():
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [w["name"] for w in SPEC["workloads"]] + [c["name"] for c in SPEC["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for w in SPEC["workloads"]:
        assert NAME.match(w["traffic"]) and ONE_LINE.match(w["why"]), w
        assert w["chips"] in (1, 4)
    for c in SPEC["configs"]:
        assert ONE_LINE.match(c["why"]) and ONE_LINE.match(c["source"])
    for m in SPEC["per_layer"]:
        assert ONE_LINE.match(m["layer"])


def test_entry_keys():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer",
                                          "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")


def e2e_of(cell):
    return {m["name"] for m in SPEC["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}


def test_every_cell_reports_setup_another_e2e_and_a_per_layer_metric():
    for w in SPEC["workloads"]:
        e2e = e2e_of(w["name"])
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(w["name"] in m.get("workloads", [w["name"]]) for m in SPEC["per_layer"])


def test_per_layer_cells_report_what_they_move():
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert m["moves"] in e2e_of(cell), (m["name"], cell)
        assert (BENCH_DIR / "metrics" / f"{m['name']}.py").exists()


def test_configs_files_and_cuts():
    used = {w["config"] for w in SPEC["workloads"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for c in SPEC["configs"]:
        assert c["name"] in used, f"{c['name']} has no cell"
        assert c["file"].startswith("benchmarks/chip/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        published = cfg.get("published", {})
        assert sorted(published) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert not WIDTHS.search(key), f"{key} is a width"
            assert cfg[key] != published[key]


def test_configs_map_to_the_program_and_name_their_reference():
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        bench.arch_of(cfg)
        reference = bench.reference_of(cfg)
        assert callable(reference.train) and callable(reference.position_logits)


def test_cells_find_their_files():
    for w in SPEC["workloads"]:
        cell = bench.load_cell(w["name"], SPEC)
        assert (BENCH_DIR / "jobs" / f"{cell.traffic['job']}.py").exists()
        assert cell.limits is not None, f"no limits/{w['name']}.json"
        four = [x for x in SPEC["workloads"] if x["chips"] == 4]
        assert len(four) <= max(1, len(SPEC["workloads"]) // 2)


def harness_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_harness_prints_no_result_off_a_tpu():
    p = subprocess.run([sys.executable, str(BENCH_DIR / "run.py"), "--workload",
                        SPEC["workloads"][0]["name"], "--seed", "3", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=harness_env(),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs a TPU" in p.stderr


def test_harness_fails_with_only_its_own_files(tmp_path):
    (tmp_path / "benchmarks").mkdir()
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks" / "chip")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py", "--workload",
                        SPEC["workloads"][0]["name"], "--seed", "3", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=harness_env(),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout


TINY_REFERENCE = '''"""A reference module that only this copy has: the llama
reference, saying when it trains."""
import sys

from reference.llama import position_logits, train as llama_train


def train(*args, **kwargs):
    print("tiny reference trains", file=sys.stderr)
    return llama_train(*args, **kwargs)
'''


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A copy of the benchmark gains configurations, traffic mixes and
    cells by new files and new entries only, one of them a configuration
    that names a reference module of its own; the harness finds and runs
    them (their jobs at a tiny size on the CPU, the chip check skipped)."""
    bench_copy = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH_DIR, bench_copy)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    tiny = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                num_key_value_heads=2, num_hidden_layers=1, vocab_size=128)
    decode_cfg = dict(json.loads((BENCH_DIR / "configs" / "yi-6b.json").read_text()),
                      name="tiny-dense", **tiny)
    train_cfg = dict(json.loads((BENCH_DIR / "configs" / "yi-6b-train-stage.json").read_text()),
                     name="tiny-train", reference="reference/tiny.py", **tiny)
    (bench_copy / "reference" / "tiny.py").write_text(TINY_REFERENCE)
    optimizer = bench.traffic.load("train.s4k.b1")["optimizer"]
    files = {
        "configs/tiny-dense.json": decode_cfg,
        "configs/tiny-train.json": train_cfg,
        "traffic/decode.tiny.json": {"job": "decode", "batch": 4, "context": 8,
                                     "turn_tokens": 4},
        "traffic/train.tiny.json": {"job": "train", "seq_len": 32, "global_batch": 2,
                                    "microbatches": 1, "optimizer": optimizer},
        "limits/tiny.decode.json": {"served_mean_gap": 0.1},
        "limits/tiny.train.json": {"loss_gap": 0.05, "grad_gap": 0.05, "grad_diff": 0.5,
                                   "change_gap": 0.5},
    }
    for path, content in files.items():
        (bench_copy / path).write_text(json.dumps(content))
    for cfg, job, like in ((decode_cfg, "decode", "yi-6b.decode.b16.c1k"),
                           (train_cfg, "train", "yi-6b.train.s4k")):
        spec["configs"].append({"name": cfg["name"], "source": cfg["source"],
                                "file": f"benchmarks/chip/configs/{cfg['name']}.json",
                                "reduced": [], "why": "a test"})
        spec["workloads"].append({"name": f"tiny.{job}", "config": cfg["name"],
                                  "traffic": f"{job}.tiny", "chips": 1, "why": "a test"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(f"tiny.{job}")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    script = textwrap.dedent(f"""
        import json, sys, time, types
        sys.path[:0] = [{str(bench_copy)!r}, {str(ROOT / 'src')!r}]
        import run
        spec = json.load(open({str(tmp_path / 'BENCHMARK.json')!r}))
        dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
        for cell in ("tiny.decode", "tiny.train"):
            line = run.run_cell(spec, cell, 5, 0.5, False, time.perf_counter(), [dev])
            print(json.dumps(line))
    """)
    p = subprocess.run([sys.executable, "-c", script], cwd=tmp_path, env=harness_env(),
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    decode_line, train_line = [json.loads(x) for x in p.stdout.strip().splitlines()[-2:]]
    assert set(decode_line["metrics"]) == {"decode_tokens_per_s", "decode_step_p95_ms",
                                           "setup_s"}
    assert set(train_line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for line in (decode_line, train_line):
        assert line["correct"] is True, line["checks"]
        assert list(line)[-1] == "checks"
    assert set(train_line["checks"]) == {"loss_gap", "grad_gap", "grad_diff", "change_gap"}
    assert "tiny reference trains" in p.stderr

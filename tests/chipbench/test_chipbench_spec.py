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


STAND_INS = '''
import dataclasses, typing
import weights
from repro.configs import base
from repro.models import lm


@dataclasses.dataclass(frozen=True)
class Granite(base.ArchConfig):
    rms_norm_eps: float = 1e-5
    embedding_multiplier: float = 1.0
    attention_multiplier: float = 0.0        # 0 -> head_dim ** -0.5
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    def __post_init__(self):
        super().__post_init__()
        if self.attention_multiplier == 0.0:
            object.__setattr__(self, "attention_multiplier", self.head_dim ** -0.5)


@dataclasses.dataclass(frozen=True)
class Dropless(lm.RunCfg):
    capacity_factor: typing.Optional[float] = 1.25


real_shapes = weights.param_shapes


def shapes(arch, dtype):
    out = real_shapes(arch, dtype)
    return {p: v for p, v in out.items() if not (arch.tie_embeddings and p == "['lm_head']")}


base.ArchConfig, lm.RunCfg, weights.param_shapes = Granite, Dropless, shapes
'''


def test_the_published_granite_cell_needs_only_new_files(tmp_path):
    """The next step for the expert model: its configuration at published
    form (tied head, eps 1e-6, the four multipliers, routing without
    drops; 8 of 32 layers), its cell, limits and a reader of its expert
    layer's scopes, by new files and entries only in a copy of the
    benchmark. The harness takes them against a program that has the
    fields (stand-ins of ``ArchConfig``, ``RunCfg`` and the parameter
    tree); no job runs, since no program honours those keys yet."""
    bench_copy = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH_DIR, bench_copy)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    stage = json.loads((BENCH_DIR / "configs" /
                        "granite-moe-3b-a800m-train-stage.json").read_text())
    published = dict(stage["published"])
    config = {k: v for k, v in stage.items() if k not in ("capacity_factor", "assumed")}
    config.update({k: v for k, v in published.items() if k != "num_hidden_layers"},
                  name="granite-3.0-3b-a800m-stage", published={"num_hidden_layers": 32},
                  why_changed={"num_hidden_layers": "one stage of four"})
    files = {
        f"configs/{config['name']}.json": config,
        "limits/granite-moe.train.s1k.json": {"loss_gap": 0.01, "grad_gap": 0.01,
                                              "grad_diff": 0.1, "change_gap": 0.1,
                                              "route_flip": 0.05},
    }
    for path, content in files.items():
        (bench_copy / path).write_text(json.dumps(content))
    (bench_copy / "metrics" / "moe_ms.train.py").write_text(
        "import scopes\n\n\ndef read(r):\n    return scopes.scope_ms(r, 'moe', 'router', "
        "'dispatch', 'experts', 'combine')\n")
    cell = "granite-moe.train.s1k"
    spec["configs"].append({"name": config["name"], "source": config["source"],
                            "file": f"benchmarks/chip/configs/{config['name']}.json",
                            "reduced": ["num_hidden_layers"], "why": "a test"})
    spec["workloads"].append({"name": cell, "config": config["name"],
                              "traffic": "train.s1k.b8", "chips": 1, "why": "a test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "yi-6b.train.s4k" in m.get("workloads", []):
            m["workloads"].append(cell)
    spec["per_layer"].append({"name": "moe_ms.train", "unit": "ms", "better": "lower",
                              "source": "device_trace", "layer": "expert layer",
                              "moves": "train_tokens_per_s", "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    assert sorted(config["published"]) == spec["configs"][-1]["reduced"]
    assert not any(WIDTHS.search(k) for k in spec["configs"][-1]["reduced"])
    script = textwrap.dedent(f"""
        import json, sys
        sys.path[:0] = [{str(bench_copy)!r}, {str(ROOT / 'src')!r}]
        import bench, run
        """) + STAND_INS + textwrap.dedent(f"""
        spec = json.load(open({str(tmp_path / 'BENCHMARK.json')!r}))
        cell = bench.load_cell({cell!r}, spec, root=bench.ROOT)
        arch = bench.arch_of(cell.config)
        dm = bench.reference_of(cell.config).dims(cell.config)
        print(json.dumps({{
            "root": str(bench.ROOT), "traffic": cell.traffic["seq_len"],
            "limits": cell.limits, "tied": arch.tie_embeddings,
            "arch": [arch.num_layers, arch.rms_norm_eps, arch.embedding_multiplier,
                     arch.attention_multiplier, arch.residual_multiplier,
                     arch.logits_scaling, arch.n_experts, arch.top_k],
            "capacity": bench.run_cfg(cell.config).capacity_factor,
            "reference": [dm["eps"], dm["embedding_multiplier"], dm["attention_multiplier"],
                          dm["residual_multiplier"], dm["logits_scaling"], dm["tied"],
                          dm["capacity_factor"]],
            "metrics": [m["name"] for m in run.cell_metrics(spec, {cell!r}, True)],
        }}))
    """)
    p = subprocess.run([sys.executable, "-c", script], cwd=tmp_path, env=harness_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["root"] == str(tmp_path) and got["traffic"] == 1024
    assert got["limits"]["route_flip"] == 0.05
    assert got["tied"] is True
    assert got["arch"] == [8, 1e-6, 12.0, 0.015625, 0.22, 6.0, 40, 8]
    assert got["capacity"] is None
    assert got["reference"] == [1e-6, 12.0, 0.015625, 0.22, 6.0, True, None]
    assert "moe_ms.train" in got["metrics"]

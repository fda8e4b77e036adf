"""The harness is driven by data: a configuration, a traffic mix, a
per-layer metric and a kernel count added as files and entries, and nothing
edited, make a new cell that runs."""
import json
import os
import subprocess
import sys

from bench import run
from bench.harness import cell as cellmod
from bench.tests import tiny


def test_throwaway_cell_from_a_temporary_directory(tmp_path):
    root = tiny.make_root(str(tmp_path), cells=("gcn-arxiv.train",))
    b = os.path.join(root, "bench")
    # a configuration
    cfg = json.load(open(os.path.join(b, "configs", "gcn-arxiv.json")))
    cfg.update(name="throwaway")
    cfg["graph"]["n"] = 900
    cfg["batch"] = 300
    json.dump(cfg, open(os.path.join(b, "configs", "throwaway.json"), "w"))
    # a traffic mix of an existing kind
    json.dump({"kind": "train", "about": "throwaway"},
              open(os.path.join(b, "traffic", "train_short.json"), "w"))
    # its limits
    json.dump(json.load(open(os.path.join(b, "limits",
                                          "gcn-arxiv.train.json"))),
              open(os.path.join(b, "limits", "throwaway.train_short.json"),
                   "w"))
    # a kernel count and a per-layer metric that uses it
    with open(os.path.join(b, "kernels", "rows.py"), "w") as f:
        f.write("def count(rows):\n    return 2.0 * rows\n")
    with open(os.path.join(b, "metrics", "rows_twice.py"), "w") as f:
        f.write("def read(ctx):\n"
                "    return ctx['kernel']('rows').count("
                "ctx['counters']['rows'])\n")
    # the CPU's row of the peak table, for this run off the chip only
    peaks = json.load(open(os.path.join(tiny.ROOT, "bench", "peaks.json")))
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    json.dump(peaks, open(os.path.join(b, "peaks.json"), "w"))
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "throwaway", "source": "test",
                             "file": "bench/configs/throwaway.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "throwaway.train_short",
                               "config": "throwaway",
                               "traffic": "train_short", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_nodes_per_s":
            m["workloads"].append("throwaway.train_short")
    bench["per_layer"].append({"name": "rows_twice", "unit": "rows",
                               "better": "higher", "source":
                               "program_counter", "layer": "entry",
                               "moves": "train_nodes_per_s",
                               "workloads": ["throwaway.train_short"]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

    c = cellmod.resolve(root, "throwaway.train_short")
    assert c.config["graph"]["n"] == 900
    assert [m["name"] for m in c.per_layer][-1] == "rows_twice"
    r = run.run_cell("throwaway.train_short", 3, 0.5, True, root=root,
                     require_tpu=False)
    assert r["correct"], r["checks"]
    epochs = r["attempted"]
    assert r["metrics"]["rows_twice"]["value"] == 2.0 * 900 * epochs
    r = run.run_cell("throwaway.train_short", 3, 0.5, False, root=root,
                     require_tpu=False)
    assert set(r["metrics"]) == {"train_nodes_per_s", "setup_s"}


def _bench(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


ARGS = ["--workload", "gcn-arxiv.train", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def test_no_tpu_no_result():
    p = _bench(ARGS, tiny.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    import shutil
    shutil.copytree(os.path.join(tiny.ROOT, "bench"),
                    os.path.join(tmp_path, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    p = _bench(ARGS, str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""

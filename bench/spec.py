"""Find a cell's parts by name: ``BENCHMARK.json`` names them, files hold them.

* ``bench/configs/<config>.json`` — one model configuration;
* ``bench/traffic/<traffic>.json`` — one traffic mix;
* ``bench/metrics/<metric>.py`` — one metric's reader;
* ``bench/references/<reference>.py`` — one plain reference;
* ``bench/peaks.json`` — the chips' peaks, keyed by ``device_kind``.

A later change adds a configuration, a mix or a metric as new files and
new entries in ``BENCHMARK.json``; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Metric:
    name: str
    kind: str  # end_to_end | per_layer
    unit: str
    reader: object  # module with read(run) -> float | None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: list[Metric]  # end_to_end then per_layer, as the cell reports


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import one file by path (names may hold dots and dashes)."""
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_part_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# the source's key -> the name the harness, the reference and the
# program's ArchConfig use
MODEL_KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
              "num_attention_heads": "n_heads",
              "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
              "intermediate_size": "d_ff", "vocab_size": "vocab",
              "rope_theta": "rope_theta", "qk_norm": "qk_norm",
              "norm_eps": "norm_eps"}


def config(name: str, root: Path = BENCH) -> dict:
    """A configuration file, with its sizes gathered under ``model``."""
    cfg = _json(root / "configs" / f"{name}.json")
    if cfg.get("name") != name:
        raise ValueError(f"configs/{name}.json names itself {cfg.get('name')!r}")
    cfg["model"] = {v: cfg[k] for k, v in MODEL_KEYS.items()}
    return cfg


def traffic(name: str, root: Path = BENCH) -> dict:
    return _json(root / "traffic" / f"{name}.json")


def reference(name: str, root: Path = BENCH):
    return load_module(root / "references" / f"{name}.py", f"ref_{name}")


def reader(name: str, root: Path = BENCH):
    """A metric's reader; checks that it declares the metric it reads."""
    mod = load_module(root / "metrics" / f"{name}.py", f"metric_{name}")
    for attr in ("UNIT", "read"):
        if not hasattr(mod, attr):
            raise ValueError(f"metrics/{name}.py lacks {attr}")
    return mod


def peaks(device_kind: str, root: Path = BENCH) -> dict:
    """Peak bf16 FLOP/s and HBM bytes/s of one chip; an unknown kind is
    an error, never a default."""
    table = _json(root / "peaks.json")["chips"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; known: "
                       f"{sorted(table)} (add it to bench/peaks.json)")
    return table[device_kind]


def benchmark(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def _covers(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def cell(name: str, root: Path = ROOT, bench_dir: Path = BENCH) -> Cell:
    """Everything one cell needs, found by the names ``BENCHMARK.json``
    gives it."""
    b = benchmark(root)
    by_name = {w["name"]: w for w in b["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(by_name)}")
    w = by_name[name]
    metrics = []
    for kind in ("end_to_end", "per_layer"):
        for m in b[kind]:
            if not _covers(m, name):
                continue
            mod = reader(m["name"], bench_dir)
            if mod.UNIT != m["unit"]:
                raise ValueError(f"metrics/{m['name']}.py says unit "
                                 f"{mod.UNIT!r}, BENCHMARK.json {m['unit']!r}")
            metrics.append(Metric(m["name"], kind, m["unit"], mod))
    return Cell(name, int(w["chips"]), config(w["config"], bench_dir),
                traffic(w["traffic"], bench_dir), metrics)

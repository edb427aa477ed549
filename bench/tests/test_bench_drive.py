"""Short drives of a whole run on the CPU at smoke sizes.

They skip the harness's look for a chip and run the rest: build, warm,
window, the reference check and the result line. A clean run must come
out correct and print no device metric; a run whose timed path is broken
underneath must come out not correct, once for each fault a one-chip
serving cell can have; the configuration's control, the cache one
precision step lower through the program's own PackKV path, must read
above the limit; and so must the reference in fp8 on the prefill
logits.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run, serve, smoke, spec

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SECONDS = 3.0
SEED = 2**31 + 77


def _cell(config="minitron-4b", traffic="long_decode"):
    return smoke.shrink(spec.Cell(f"{config}.{traffic}", 1,
                                  spec.config(config), spec.traffic(traffic),
                                  []))


def _measure(cell, capsys, packkv=None):
    res = run.measure(cell, SEED, SECONDS, False, CPU, None, packkv)
    line = json.loads(json.dumps(res))  # the printed form
    return line, capsys.readouterr()


@pytest.mark.parametrize("config,traffic", [
    ("minitron-4b", "long_decode"), ("qwen3-32b-8l", "long_decode_8k"),
    ("minitron-4b", "chat")])
def test_clean_run_is_correct_and_names_no_device_metric(config, traffic,
                                                         capsys):
    line, out = _measure(_cell(config, traffic), capsys)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["metrics"] == {}  # off the chip: no device metric at all
    assert line["device"]["platform"] == "cpu"
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "programs built or loaded inside the window: 0" in out.out
    assert "check logit_gap" in out.err.strip().splitlines()[-1] or \
        any(l.startswith("check logit_gap") for l in out.err.splitlines())


def _break(monkeypatch, fault):
    """Build engines whose decode launch has ``fault`` underneath."""
    build = serve.build

    def broken(cfg, seed, ref, packkv=None):
        eng, params = build(cfg, seed, ref, packkv)
        real = eng.decode_chunk

        def launch(cache, token, active, n_steps, eos_id, n_bucket=None):
            if fault == "state unchanged":
                keep = jax.tree.map(jnp.copy, cache)
                toks, n, _ = real(cache, token, active, n_steps, eos_id,
                                  n_bucket)
                return toks, n, keep
            toks, n, cache = real(cache, token, active, n_steps, eos_id,
                                  n_bucket)
            toks = np.array(toks)
            rows = np.flatnonzero(active)
            if not rows.size:  # the warm-up's idle launches
                return toks, n, cache
            if fault == "token altered":
                toks[0, rows[0]] = (toks[0, rows[0]] + 1) % eng.cfg.vocab
            elif fault == "half the batch left out":
                half = rows[len(rows) // 2:]
                toks[:, half] = np.asarray(token)[half, 0]
            return toks, n, cache

        eng.decode_chunk = launch
        return eng, params

    monkeypatch.setattr(serve, "build", broken)


@pytest.mark.parametrize("fault", ["state unchanged", "token altered",
                                   "half the batch left out"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch, capsys):
    _break(monkeypatch, fault)
    line, _ = _measure(_cell(), capsys)
    assert not line["correct"], (fault, line["checks"])


def test_lower_precision_cache_control_fails_the_limit(capsys):
    cell = _cell()
    line, _ = _measure(cell, capsys, cell.config["control"]["packkv"])
    gap = line["checks"]["logit_gap"]
    assert gap["value"] > gap["max"], gap


def test_fp8_reference_control_fails_the_prefill_limit(capsys):
    res = run.measure(_cell(), SEED, SECONDS, False, CPU, None, lower=True)
    checks = res["checks"]
    assert res["correct"], checks
    limit = checks["prefill_logit_err"]["max"]
    assert checks["prefill_logit_err"]["value"] <= limit
    assert checks["fp8_prefill_logit_err"]["value"] > limit, checks

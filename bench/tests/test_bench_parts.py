"""The benchmark's parts on the CPU: traffic, metric arithmetic, discovery,
peaks, work counts and the reference's weights. Nothing here describes a
chip or reads a device metric."""
import json
import shutil
import types

import numpy as np
import pytest

from bench import serve, spec, traffic, work

LONG = spec.traffic("long_decode")
CHAT = dict(spec.traffic("chat"), rate_per_s=2.0)


# -- traffic ------------------------------------------------------------------

def test_same_seed_same_traffic():
    a, b = traffic.arrivals(CHAT, 30), traffic.arrivals(CHAT, 30)
    assert [(x.due_s, x.card) for x in a] == [(x.due_s, x.card) for x in b]
    assert traffic.deck(LONG, 3) == traffic.deck(LONG, 3)
    np.testing.assert_array_equal(traffic.prompt_tokens(5, 3, 64, 1000),
                                  traffic.prompt_tokens(5, 3, 64, 1000))


def test_seeds_change_tokens_and_never_the_work():
    # sizes and arrivals are one fixed order; the seed draws token ids
    assert not np.array_equal(traffic.prompt_tokens(2**31 + 1, 0, 64, 1000),
                              traffic.prompt_tokens(2**31 + 2, 0, 64, 1000))
    d0, d1 = traffic.deck(LONG, 0), traffic.deck(LONG, 1)
    assert d0 != d1 and sorted(d0, key=str) == sorted(d1, key=str)
    assert len(d0) == LONG["deck"]
    # the joint distribution in exact proportions: 0.3 x 0.4 of 50 is 6
    assert d0.count(traffic.Card(1024, 128)) == 6
    a = traffic.arrivals(CHAT, 30)
    assert len(a) == round(40 * 2.0)  # warm-up 10 s + 30 s
    assert all(x.due_s < 30 for x in a)
    assert [x.due_s for x in a] == sorted(x.due_s for x in a)


def test_bursts_add_arrivals():
    mix = dict(CHAT, burst={"every_s": 5.0, "size": 3}, warmup_s=0.0)
    base = len(traffic.arrivals(dict(mix, burst="none"), 20))
    assert len(traffic.arrivals(mix, 20)) == base + 3 * 3


# -- end-to-end arithmetic on hand-made timelines -------------------------------

def _req(times, t_first=None, status="done", due=0.0, sent=0.0, done_at=None):
    req = types.SimpleNamespace(token_times=list(times), t_first=t_first,
                                status=status, tokens=np.zeros(4),
                                output=np.zeros(len(times)))
    return serve.Rec(0, 4, len(times), due, sent, req, done_at)


def _run(recs, t0=10.0, t1=20.0):
    return serve.Run(model={}, n_slots=4, pool_pages=8, page_size=64,
                     block=64, residual=128, capacity=256, t_start=0.0,
                     t0=t0, t1=t1, stats0={}, stats1={}, recs=recs,
                     logits={}, launches=[],
                     prefills=[], cache_bytes=0, comp_bytes_per_token=0.0,
                     compiles_in_window=0, lateness=[])


def test_out_tok_s_counts_tokens_inside_the_window():
    r = _req([9.0] * 8 + [11.0] * 8 + [21.0] * 8)
    out = spec.reader("out_tok_s").read(_run([r]))
    assert out == pytest.approx(8 / 10)


def test_tpot_with_shared_launch_stamps():
    # launches of 8 tokens every 2 s: 2 s per 8 tokens = 250 ms a token
    r = _req([9.0] * 8 + [11.0] * 8 + [13.0] * 8 + [15.0] * 8)
    tp = spec.reader("tpot_p90_ms")
    assert tp.per_request(_run([r])) == [pytest.approx(250.0)]
    one_stamp = _req([12.0] * 8)  # a single launch in the window: no time
    assert tp.per_request(_run([one_stamp])) == []
    assert tp.read(_run([one_stamp])) is None


def test_ttft_from_due_time_and_censored_waits():
    tt = spec.reader("ttft_p90_ms")
    served = _req([12.5], t_first=12.5, due=12.0)
    waiting = _req([], t_first=None, status="queued", due=18.0)
    before = _req([10.5], t_first=10.5, due=9.0)  # due before the window
    late_first = _req([], t_first=25.0, status="active", due=19.0)
    got = tt.per_request(_run([served, waiting, before, late_first]))
    assert got == [pytest.approx(500.0), pytest.approx(2000.0),
                   pytest.approx(1000.0)]


def test_shares_and_setup():
    run = _run([])
    run.stats0 = {"decode_steps": 10, "occupied_slot_steps": 30,
                  "pages_reserved_peak": 0}
    run.stats1 = {"decode_steps": 30, "occupied_slot_steps": 90,
                  "pages_reserved_peak": 6}
    assert spec.reader("slot_occupancy").read(run) == pytest.approx(60 / 80)
    assert spec.reader("pool_peak_share").read(run) == pytest.approx(6 / 8)
    assert spec.reader("setup_s").read(run) == pytest.approx(10.0)
    assert spec.reader("idle_share").read(run) is None  # no trace: nothing


# -- discovery ----------------------------------------------------------------

def test_new_config_traffic_and_metric_are_found_from_files(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(spec.BENCH / "configs", bench / "configs")
    shutil.copytree(spec.BENCH / "traffic", bench / "traffic")
    shutil.copytree(spec.BENCH / "metrics", bench / "metrics")
    cfg = json.loads((bench / "configs" / "minitron-4b.json").read_text())
    cfg["name"] = "tiny-model"
    (bench / "configs" / "tiny-model.json").write_text(json.dumps(cfg))
    mix = dict(LONG, clients=2)
    (bench / "traffic" / "two_clients.json").write_text(json.dumps(mix))
    (bench / "metrics" / "queue_wait.serve.py").write_text(
        'UNIT = "ms"\n\ndef read(run):\n    return 1.5\n')
    b = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "tiny-model.two", "config": "tiny-model",
                           "traffic": "two_clients", "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "queue_wait.serve", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "scheduler", "moves": "ttft_p90_ms",
                           "workloads": ["tiny-model.two"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.cell("tiny-model.two", root=tmp_path, bench_dir=bench)
    assert cell.config["name"] == "tiny-model"
    assert cell.traffic["clients"] == 2
    names = [m.name for m in cell.metrics]
    assert "queue_wait.serve" in names and "ttft_p90_ms" not in names
    m = next(m for m in cell.metrics if m.name == "queue_wait.serve")
    assert m.reader.read(None) == 1.5
    with pytest.raises(KeyError):
        spec.cell("no.such.cell", root=tmp_path, bench_dir=bench)


def test_every_cell_and_metric_of_the_benchmark_resolves():
    b = spec.benchmark()
    for w in b["workloads"]:
        cell = spec.cell(w["name"])
        assert cell.chips == w["chips"]
        kinds = {m.kind for m in cell.metrics}
        assert kinds == {"end_to_end", "per_layer"}
    for m in b["per_layer"]:
        mod = spec.reader(m["name"])
        assert (mod.LAYER, mod.MOVES) == (m["layer"], m["moves"])


def test_peaks_refuse_an_unknown_chip():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")


# -- work counts and the cache's size ------------------------------------------

def test_packkv_counters_follow_block_flushes():
    # prompt 1000: 960 compressed, 40 in the residual
    assert work.packkv_counters(1000, 1000, 64, 128, 4096) == (960, 40)
    assert work.packkv_counters(1000, 1088, 64, 128, 4096) == (960, 128)
    assert work.packkv_counters(1000, 1089, 64, 128, 4096) == (1024, 65)
    assert work.packkv_counters(4000, 4200, 64, 128, 4096) == (4096, 104)


def test_prefill_flops_split_into_chunks_add_up():
    m = spec.config("minitron-4b")["model"]
    whole = work.prefill_flops(m, 3072, 0, True)
    parts = (work.prefill_flops(m, 1024, 0, False)
             + work.prefill_flops(m, 1024, 1024, False)
             + work.prefill_flops(m, 1024, 2048, True))
    assert whole == parts


@pytest.mark.parametrize("slots,capacity,pages,per_token", [
    (16, 4096, 256, 51265), (12, 3584, 168, 51859), (16, 4096, 160, 53761)])
def test_kv_bytes_per_token_at_the_4bit_tier(slots, capacity, pages,
                                             per_token):
    import jax

    from repro.core.cache import PackKVConfig
    from repro.core.tiered import TierSpec
    from repro.models import get_model

    cfg = spec.config("minitron-4b")
    arch = serve.arch_of(cfg)
    tier = TierSpec(widths=(4,), counts=(128,))
    pack = PackKVConfig(**cfg["packkv"], k_spec_static=tier,
                        v_spec_static=tier, paged=True, page_size=256,
                        pool_pages=pages)
    cache = jax.eval_shape(lambda: get_model(arch).alloc_cache(
        arch, pack, slots, capacity))
    run = _run([])
    run.cache_bytes = sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(cache))
    run.n_slots, run.capacity = slots, capacity
    run.pool_pages, run.page_size = pages, 256
    assert int(spec.reader("kv_bytes_per_token").read(run)) == per_token


# -- the reference's weights ----------------------------------------------------

def test_reference_rebuilds_the_served_weights_layer_by_layer():
    import jax

    from bench import smoke

    cell = smoke.shrink(spec.cell("qwen3-32b-8l.long_decode"))
    m = cell.config["model"]
    ref = spec.reference("llama_block")
    seed = 2**33 + 5
    full = ref.program_params(seed, m)
    for layer in range(m["n_layers"]):
        one = ref.layer_params(seed, m, layer)
        got = jax.tree.map(lambda x: x[layer], full["layers"])
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b)), got, one)
    g = ref.layer_params(seed, m, None)
    np.testing.assert_array_equal(np.asarray(full["head"], np.float32),
                                  np.asarray(g["head"]))
    # the two 32-bit halves of a seed both count
    other = ref.program_params(5, m)
    assert not np.array_equal(np.asarray(other["head"]),
                              np.asarray(full["head"]))


def test_served_layout_is_checked():
    from bench import smoke

    cell = smoke.shrink(spec.cell("minitron-4b.long_decode"))
    ref = spec.reference("llama_block")

    def short(seed, m):  # a layout without the output head
        p = ref.program_params(seed, m)
        del p["head"]
        return p

    with pytest.raises(ValueError, match="served layout"):
        serve.build(cell.config, 1, types.SimpleNamespace(program_params=short))

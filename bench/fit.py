"""Does a configuration fit one v5e? Compile its served programs for a
described chip (nothing runs) and print each one's ``memory_analysis``.

    JAX_PLATFORMS=cpu python bench/fit.py --config minitron-4b \\
        --prompts 1024,2048,3072

Compiles, at the configuration's full size with one 4-bit tier for K and
V (what calibration picks on the benchmark's weights), the decode launch
at the largest length bucket, the one-shot admission of the longest
prompt that takes it and, for prompts longer than one admission chunk, a middle chunk
and the fused final chunk of the longest prompt. Weights and the slot
cache are the programs' arguments, so a program fits where its argument,
output and temporary bytes, less what it aliases, fit under the chip's
memory. Run on the CPU: the TPU compiler is installed with JAX.
"""
from __future__ import annotations

import argparse
import os
import sys
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--prompts", required=True,
                    help="comma-separated prompt lengths the traffic sends")
    ap.add_argument("--max-batch", type=int, help="slots, if not the file's")
    ap.add_argument("--capacity", type=int, help="tokens a slot holds, if "
                    "not the file's")
    ap.add_argument("--pool-pages", type=int, help="pages of the paged "
                    "pool, if not the file's")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import serve, spec
    from repro.core.cache import PackKVConfig
    from repro.core.tiered import TierSpec
    from repro.kernels import pallas_utils
    from repro.models import get_model

    jax.config.update("jax_enable_compilation_cache", False)
    pallas_utils.interpret_mode = lambda: False
    dev = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    cfg = spec.config(args.config)
    arch = serve.arch_of(cfg)
    api = get_model(arch)
    e = cfg["engine"]
    e["max_batch"] = args.max_batch or e["max_batch"]
    e["capacity"] = args.capacity or e["capacity"]
    e["pool_pages"] = args.pool_pages or e.get("pool_pages") or (
        e["max_batch"] * e["capacity"] // e["page_size"])
    tier = TierSpec(widths=(4,), counts=(arch.hd,))
    pack = PackKVConfig(**cfg["packkv"], k_spec_static=tier,
                        v_spec_static=tier, paged=True,
                        page_size=e["page_size"],
                        pool_pages=e["pool_pages"])
    on = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=dev), tree)
    params = on(jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0), arch)))
    cache = on(jax.eval_shape(lambda: api.alloc_cache(
        arch, pack, e["max_batch"], e["capacity"])))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=dev)
    B = e["max_batch"]
    nbytes = lambda t: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))
    print(f"{args.config}: {B} slots x {e['capacity']} tokens, pool "
          f"{e['pool_pages']} pages; params {nbytes(params):,} B, slot cache "
          f"{nbytes(cache):,} B")

    def report(name, lowered):
        try:
            m = lowered.compile().memory_analysis()
        except jax.errors.JaxRuntimeError as err:
            print(f"{name}: does not fit: {str(err).splitlines()[0]}",
                  flush=True)
            return
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(f"{name}: arguments {m.argument_size_in_bytes:,} outputs "
              f"{m.output_size_in_bytes:,} temporaries "
              f"{m.temp_size_in_bytes:,} aliased {m.alias_size_in_bytes:,} "
              f"-> {total:,} B ({total / 2**30:.2f} GiB)", flush=True)

    dec = jax.jit(partial(api.decode_multi, cfg=arch, backend=e["backend"]),
                  static_argnames=("t_max", "n_bucket"),
                  donate_argnames=("cache",))
    report(f"decode launch (bucket {e['capacity']})", dec.lower(
        params, cache=cache, token=i32(B, 1),
        active=jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=dev),
        n_steps=i32(), eos_id=i32(), t_max=e["decode_chunk"],
        n_bucket=e["capacity"]))
    prompts = sorted(int(p) for p in args.prompts.split(","))
    chunk = e["prefill_chunk_pages"] * e["page_size"]
    ins = jax.jit(partial(api.prefill_into_slot, cfg=arch, pack_cfg=pack,
                          capacity=e["capacity"]))
    p0 = max([p for p in prompts if p <= chunk] or [chunk])
    report(f"one-shot admission ({p0} tokens)", ins.lower(
        params, cache=cache, slot=i32(), batch={"tokens": i32(1, p0)}))
    P = prompts[-1]
    if P > chunk:
        scratch = on(jax.eval_shape(lambda: api.prefill_chunk_init(
            arch, pack, e["capacity"], prompt_len=P)))
        step = jax.jit(partial(api.prefill_chunk, cfg=arch, pack_cfg=pack),
                       static_argnames=("n_ctx",))
        report(f"admission chunk ({chunk} of {P} tokens)", step.lower(
            params, scratch=scratch, tokens=i32(1, chunk), n_ctx=P - 2 * chunk))

        def final(params, cache, slot, scratch, tokens, n_ctx):
            logits, scratch = api.prefill_chunk(
                params, scratch=scratch, tokens=tokens, n_ctx=n_ctx, cfg=arch,
                pack_cfg=pack)
            return logits, api.prefill_chunk_insert(
                cache=cache, slot=slot, scratch=scratch, cfg=arch,
                pack_cfg=pack, capacity=e["capacity"])

        report(f"final admission chunk ({chunk} of {P} tokens)",
               jax.jit(final, static_argnames=("n_ctx",)).lower(
                   params, cache, i32(), scratch, i32(1, chunk),
                   n_ctx=P - chunk))
    return 0


if __name__ == "__main__":
    sys.exit(main())

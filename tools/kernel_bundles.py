"""Static schedule of the paged attention kernel for a TPU v5e, with no chip.

Compiles one ``fused_packed_attention_paged`` launch for a described
``v5e:2x2`` (one of its chips) with the TPU compiler's dump on, then reads
the kernel's final schedule: the number of scheduled bundles (VLIW
instruction words) and a histogram of the operations in them. A decode
tile's body is straight-line code, so the bundle count tracks the cost of
one live grid step; it is a count, not a time. The launch is
``minitron-4b.long_decode``'s (16 rows, 8 kv heads, a 4096-token bucket of
256-token pages, a 160-page pool) at the given G and tier spec. A step's
vector body depends on G, the spec and the page; the rows, bucket and pool
move only scalar address code (at ``qwen3-32b-8l.long_decode``'s launch,
also of 256-token pages, G 8 reads about 100 bundles more).

Run from the repository root (the TPU compiler comes with JAX):

    python tools/kernel_bundles.py                       # G 3, one 4-bit tier
    python tools/kernel_bundles.py --g 8
    python tools/kernel_bundles.py --widths 2,4,8 --counts 32,64,32
    python tools/kernel_bundles.py --src <other checkout>/src

The compile runs in a child process: the dump flag must be in
``LIBTPU_INIT_ARGS`` before the TPU library loads, and with the dump on
the library may abort after the kernel's schedule is written (it looks for
a report template it does not ship), so the dump, not the exit code, says
whether the kernel compiled. The last line printed is one JSON object.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import re
import subprocess
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
KERNEL = "packkv_paged_attention"
# minitron-4b.long_decode's launch: rows, kv heads, bucket, page, pool pages
BATCH, KV_HEADS, BUCKET, PAGE, POOL_PAGES = 16, 8, 4096, 256, 160
TOP = 25  # operations listed in the histogram
TOTAL_RE = re.compile(r"total scheduled bundles:\s*(\d+)")
BUNDLE_RE = re.compile(r"\s*0x[0-9a-f]+\s+[A-Z]*:?\s*>?\s*\{(.*)\}")
OP_RE = re.compile(r"(?:%\w+\s*=\s*)?([A-Za-z_][\w.]*)")


def _ints(text: str) -> tuple[int, ...]:
    return tuple(int(x) for x in text.split(","))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--g", type=int, default=3, help="q heads per kv head")
    p.add_argument("--widths", type=_ints, default=(4,),
                   help="tier widths of K and of V, e.g. 2,4,8")
    p.add_argument("--counts", type=_ints, default=(128,),
                   help="channels per tier, summing to the head size")
    p.add_argument("--src", type=Path, default=REPO / "src",
                   help="source tree whose kernel is compiled")
    p.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    return p.parse_args(argv)


def compile_launch(a) -> None:
    """Child: compile one paged launch for a described v5e chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(a.src))
    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.core.tiered import TierSpec, alloc_tiered_pool
    from repro.kernels import pallas_utils
    from repro.kernels.packed_attention import fused_packed_attention_paged

    pallas_utils.interpret_mode = lambda: False  # lower through Mosaic
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    spec = TierSpec(widths=a.widths, counts=a.counts)
    D = spec.head_dim
    pool = jax.eval_shape(lambda: alloc_tiered_pool(
        BATCH, KV_HEADS, POOL_PAGES, PAGE, spec))
    args = [jax.ShapeDtypeStruct((BATCH, KV_HEADS * a.g, D), jnp.float32),
            pool, pool,
            jax.ShapeDtypeStruct((BATCH, BUCKET // PAGE), jnp.int32),
            jax.ShapeDtypeStruct((BATCH,), jnp.int32)]
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), args)
    fn = partial(fused_packed_attention_paged, n_tokens=BUCKET,
                 sm_scale=D ** -0.5, page_size=PAGE)
    jax.jit(fn).lower(*args).compile()


def read_schedule(dump: Path) -> dict:
    """Bundle count and op histogram of the kernel's final schedule."""
    analysis = list(
        dump.glob(f"*{KERNEL}*-schedule-analysis_final_bundles.txt"))
    bundles = [f for f in dump.glob(f"*{KERNEL}*-final_bundles.txt")
               if "schedule-analysis" not in f.name]
    if len(analysis) != 1 or len(bundles) != 1:
        raise SystemExit(f"expected one final schedule of {KERNEL} in {dump}, "
                         f"found {len(analysis)} and {len(bundles)}")
    total = TOTAL_RE.search(analysis[0].read_text())
    ops = collections.Counter()
    for line in bundles[0].read_text().splitlines():
        m = BUNDLE_RE.match(line)
        if not m:
            continue
        for ins in m.group(1).split(";;"):
            ins = re.sub(r"/\*.*?\*/", "", ins).strip()
            op = OP_RE.match(ins)
            if op:
                ops[op.group(1)] += 1
    return {"bundles": int(total.group(1)) if total else None,
            "ops": dict(ops.most_common(TOP))}


def main(argv=None) -> int:
    a = parse_args(argv)
    if a.child:
        compile_launch(a)
        return 0
    (REPO / "build").mkdir(exist_ok=True)
    # the dump is a few hundred MB: read it, then delete it
    with tempfile.TemporaryDirectory(dir=REPO / "build") as tmp:
        dump = Path(tmp)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["LIBTPU_INIT_ARGS"] = " ".join(filter(None, [
            os.environ.get("LIBTPU_INIT_ARGS"), f"--xla_jf_dump_to={dump}"]))
        child = [sys.executable, __file__, "--child", *(argv or sys.argv[1:])]
        run = subprocess.run(child, env=env, capture_output=True, text=True)
        if not any(dump.glob(f"*{KERNEL}*-final_bundles.txt")):
            sys.stderr.write(run.stdout[-4000:] + run.stderr[-4000:])
            raise SystemExit(f"the compile failed (exit {run.returncode})")
        schedule = read_schedule(dump)
    out = {"g": a.g, "widths": list(a.widths), "counts": list(a.counts),
           "src": str(a.src), **schedule}
    for op, n in out["ops"].items():
        print(f"{n:8d}  {op}")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

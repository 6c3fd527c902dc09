"""Compile a cell's programs for a described v5e chip, with no chip here.

    JAX_PLATFORMS=cpu python bench/rehearse.py internlm2-1.8b.chat

Compiles, for one chip of a described `v5e:2x2` topology, the paged
prefill of every bucket of the cell's engine, its decode at full width,
and the reference that the cell's configuration names for the output
check (its `lower_gaps`), and prints each program's
`memory_analysis()` bytes.  It raises what the chip's compiler would
raise.  Nothing runs, so it says nothing about times or results.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(cell_name: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness
    from repro.models import api
    from repro.serving import paged

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(cell_name)
    geo = cell.geometry
    cfg = harness.model_config(cell.config)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    params = jax.tree.map(sds, jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0))))
    harness.check_reads(cell.reference, params)
    ps, slots, max_len = geo["page_size"], geo["slots"], geo["max_len"]
    npp = -(-max_len // ps)
    num_pages = 1 + slots * npp
    segs = jax.tree.map(sds, jax.eval_shape(lambda: api.init_paged_cache(cfg, num_pages, ps)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa: E731

    def report(what, compiled):
        m = compiled.memory_analysis()
        print(f"{what}: args {m.argument_size_in_bytes} out {m.output_size_in_bytes} "
              f"temp {m.temp_size_in_bytes} alias {m.alias_size_in_bytes} "
              f"total {m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes - m.alias_size_in_bytes}",
              flush=True)

    for b in paged.prefill_buckets(max_len, 16):
        fn = paged.paged_prefill_fn(cfg, b, ps)
        report(f"prefill bucket {b}", fn.lower(params, i32(1, b), i32(), segs, i32(b // ps)).compile())
    dfn = paged.paged_decode_fn(cfg)
    report(f"decode width {slots}",
           dfn.lower(params, i32(slots, 1), segs, i32(slots, npp), i32(slots)).compile())

    report(f"reference length {max_len}",
           cell.reference.lower_gaps(cell.config["model"], params, i32(max_len)).compile())


if __name__ == "__main__":
    main(sys.argv[1])

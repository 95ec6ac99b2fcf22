"""Dry-run tooling: the collective-bytes HLO parser, config overrides,
input/cache specs, DOT + SVG exports."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.launch.dryrun import collective_stats, config_for_dryrun
from repro.launch.mesh import make_host_mesh


SAMPLE_HLO = """
HloModule test
ENTRY %main {
  %ag = bf16[256,1024]{1,0} all-gather(%p0), channel_id=1, replica_groups=[16,16]<=[256]T(1,0), dimensions={0}
  %ar = f32[128,128]{1,0} all-reduce(%x), channel_id=2, replica_groups={{0,1,2,3}}, to_apply=%add
  %rs = bf16[16,1024]{1,0} reduce-scatter(%y), channel_id=3, replica_groups=[16,16]<=[256]T(1,0), dimensions={0}
  %agst = (bf16[8,4]{1,0}, bf16[128,4]{1,0}) all-gather-start(%z), channel_id=4, replica_groups=[16,16]<=[256]
  %cp = u32[64]{0} collective-permute(%w), channel_id=5, source_target_pairs={{0,1}}
  %fusion.1 = f32[4]{0} fusion(%a), kind=kLoop
}
"""


def test_collective_parser_kinds_and_bytes():
    s = collective_stats(SAMPLE_HLO)
    assert s["all-gather"]["count"] == 2  # plain + -start
    assert s["all-gather"]["bytes"] == 256 * 1024 * 2 + 128 * 4 * 2
    assert s["all-reduce"]["count"] == 1
    assert s["all-reduce"]["bytes"] == 128 * 128 * 4
    # reduce-scatter operand = result × group_size (16)
    assert s["reduce-scatter"]["bytes"] == 16 * 1024 * 2 * 16
    assert s["collective-permute"]["count"] == 1
    assert s["total_count"] == 5
    # wire estimates: AR counts 2×(g−1)/g
    assert s["all-reduce"]["wire_bytes"] == 2 * 128 * 128 * 4 * 3 // 4


def test_config_overrides_flat_and_nested():
    cfg = config_for_dryrun("qwen3-moe-235b-a22b", {"n_layers": 4, "moe.dispatch": "scatter"})
    assert cfg.n_layers == 4
    assert cfg.moe.dispatch == "scatter"
    assert cfg.opt_state_dtype == "bfloat16"  # arch-specific dry-run default


def test_input_and_cache_specs_cover_all_cells():
    from repro.configs import ARCH_NAMES, get_config
    from repro.models import abstract_cache, abstract_inputs, applicable_shapes

    n = 0
    for arch in ARCH_NAMES:
        cfg = get_config(arch)
        for shape in applicable_shapes(cfg):
            specs = abstract_inputs(cfg, shape)
            assert all(
                isinstance(leaf, jax.ShapeDtypeStruct) for leaf in jax.tree.leaves(specs)
            )
            if shape.kind == "decode":
                cache = abstract_cache(cfg, shape.global_batch, shape.seq_len)
                assert jax.tree.leaves(cache)
            n += 1
    assert n == 37  # the cell count after skip rules (31 assigned + 2 x 3 DeepSeek-V2-Lite)


def test_applicable_shape_rules():
    from repro.configs import get_config
    from repro.models import applicable_shapes

    names = lambda a: [s.name for s in applicable_shapes(get_config(a))]
    assert names("hubert-xlarge") == ["train_4k", "prefill_32k"]  # encoder-only
    assert "long_500k" not in names("gemma-7b")  # full attention
    assert "long_500k" in names("mamba2-130m")
    assert "long_500k" in names("recurrentgemma-9b")


def test_dot_and_trace_export(tmp_path):
    from repro.core import SpComputeEngine, SpData, SpRead, SpTaskGraph, SpWorkerTeamBuilder, SpWrite

    eng = SpComputeEngine(SpWorkerTeamBuilder.team_of_cpu_workers(2))
    try:
        tg = SpTaskGraph().compute_on(eng)
        a, b = SpData(1, "a"), SpData(0, "b")
        tg.task(SpRead(a), SpWrite(b), lambda v, r: setattr(r, "value", v + 1), name="t1")
        tg.task(SpRead(b), lambda v: v, name="t2")
        tg.wait_all_tasks()
        dot = tg.generate_dot(str(tmp_path / "g.dot"), show_accesses=True)
        assert "t1" in dot and "->" in dot and "read:b" in dot
        svg = tg.generate_trace(str(tmp_path / "g.svg"))
        assert svg.startswith("<svg") and "t1" in svg
    finally:
        eng.stop()


def test_trace_metrics():
    import time

    from repro.core import (
        SpComputeEngine,
        SpData,
        SpRead,
        SpTaskGraph,
        SpWorkerTeamBuilder,
        trace_metrics,
    )

    eng = SpComputeEngine(SpWorkerTeamBuilder.team_of_cpu_workers(2))
    try:
        tg = SpTaskGraph().compute_on(eng)
        x = SpData(1, "x")
        for _ in range(4):
            tg.task(SpRead(x), lambda v: time.sleep(0.01))
        tg.wait_all_tasks()
        m = trace_metrics(tg)
        assert m["n_tasks"] == 4
        assert 0 < m["utilization"] <= 1.0
        assert m["mean_task_us"] >= 9000
    finally:
        eng.stop()

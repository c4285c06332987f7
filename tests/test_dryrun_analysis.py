"""Dry-run analysis plumbing: HLO collective parser + roofline math.

Imports repro.launch.hlo_analysis (NOT dryrun, whose import sets XLA_FLAGS
for 512 placeholder devices — a side effect no test process wants).
"""

import pytest

import repro.core  # noqa: F401
from repro.launch.hlo_analysis import (collective_bytes_from_hlo,
                                       count_fusions, parse_replica_groups)
from repro.launch.mesh import make_mesh
from benchmarks.roofline import analyze_record, model_flops


# modern HLO style: operands are SSA refs without inline shapes
HLO = """
  %all-reduce.5 = f32[512,1024]{1,0} all-reduce(%add.3), replica_groups={{0,1},{2,3}}
  %ag = bf16[64,128]{1,0} all-gather(%p0), replica_groups=[8,16]<=[128], dimensions={0}
  %rs.1 = f32[16]{0} reduce-scatter(%x), replica_groups={{0,1,2,3}}, dimensions={0}
  %cp = u32[8,8]{1,0} collective-permute(%y), source_target_pairs={{0,1}}
  %ar2 = f32[4]{0} all-reduce-start(%z), replica_groups={{0,1}}
  %ar2d = f32[4]{0} all-reduce-done(%ar2)
  %a2a = u32[2,2]{1,0} all-to-all(%v), replica_groups={{0,1,2,3}}
  %not = f32[9]{0} add(%a, %b)
"""


def test_collective_parser_counts_and_ring_bytes():
    r = collective_bytes_from_hlo(HLO)
    assert r["counts"] == {"all-reduce": 2, "all-gather": 1,
                           "reduce-scatter": 1, "all-to-all": 1,
                           "collective-permute": 1}
    S_ar = 512 * 1024 * 4
    assert r["bytes"]["all-reduce"] == 2 * S_ar * (2 - 1) / 2 + 2 * 16 * 0.5
    assert r["bytes"]["all-gather"] == 64 * 128 * 2 * 15 / 16
    assert r["bytes"]["reduce-scatter"] == 16 * 4 * 3    # S_out·(g-1)
    assert r["bytes"]["all-to-all"] == 2 * 2 * 4 * 3 / 4
    assert r["bytes"]["collective-permute"] == 8 * 8 * 4
    assert r["total_bytes"] == sum(r["bytes"].values())


def test_parse_replica_groups_literal_and_empty():
    g, s = parse_replica_groups("replica_groups={{0,1},{2,3}}")
    assert g == [(0, 1), (2, 3)] and s == 2
    g, s = parse_replica_groups("replica_groups={{0,1,2,3},{4,5,6,7}}")
    assert g == [(0, 1, 2, 3), (4, 5, 6, 7)] and s == 4
    # empty form = one group of every participant; size falls back to
    # the program's device count when the caller knows it
    g, s = parse_replica_groups("replica_groups={}")
    assert g is None and s == 1
    g, s = parse_replica_groups("replica_groups={}", default_group_size=8)
    assert g is None and s == 8
    # no replica_groups attribute at all (collective-permute lines)
    g, s = parse_replica_groups("source_target_pairs={{0,1}}")
    assert g is None and s == 1


def test_parse_replica_groups_iota_forms():
    # [G,S]<=[N]: iota(8) reshaped (2,4) — contiguous groups
    g, s = parse_replica_groups("replica_groups=[2,4]<=[8]")
    assert s == 4
    assert g == [(0, 1, 2, 3), (4, 5, 6, 7)]
    # transposed iota: groups are the COLUMNS of iota(8)->(2,4) — this
    # is what GSPMD emits for the model axis of a ("data","model") mesh
    g, s = parse_replica_groups("replica_groups=[4,2]<=[2,4]T(1,0)")
    assert s == 2
    assert g == [(0, 4), (1, 5), (2, 6), (3, 7)]
    # identity transpose == plain iota
    g, s = parse_replica_groups("replica_groups=[2,4]<=[2,4]T(0,1)")
    assert g == [(0, 1, 2, 3), (4, 5, 6, 7)] and s == 4
    # inconsistent dims (product mismatch): size still parsed, no groups
    g, s = parse_replica_groups("replica_groups=[2,4]<=[4]")
    assert g is None and s == 4


def test_collective_parser_iota_group_wire_bytes():
    # ring bytes must use the iota group SIZE (4), not the device total
    r = collective_bytes_from_hlo(
        "%ar = f32[8,8]{1,0} all-reduce(%x), replica_groups=[2,4]<=[8]")
    assert r["counts"]["all-reduce"] == 1
    assert r["bytes"]["all-reduce"] == 2 * (8 * 8 * 4) * 3 / 4
    (rec,) = r["ops"]
    assert rec["group_size"] == 4 and rec["n_groups"] == 2
    assert rec["groups"] == [[0, 1, 2, 3], [4, 5, 6, 7]]


def test_collective_parser_async_tuple_output_half():
    # an all-gather-start tuple is (operands..., outputs...): only the
    # output half is sized, and the -done line adds nothing
    hlo = """
      %ags = (f32[8,16]{1,0}, f32[16,16]{1,0}) all-gather-start(%p), replica_groups={{0,1}}
      %agd = f32[16,16]{1,0} all-gather-done(%ags)
    """
    r = collective_bytes_from_hlo(hlo)
    assert r["counts"] == {"all-reduce": 0, "all-gather": 1,
                           "reduce-scatter": 0, "all-to-all": 0,
                           "collective-permute": 0}
    (rec,) = r["ops"]
    assert rec["async"] and rec["size_bytes"] == 16 * 16 * 4
    assert r["bytes"]["all-gather"] == 16 * 16 * 4 * (2 - 1) / 2


def test_collective_parser_unknown_dtype_still_counted():
    r = collective_bytes_from_hlo(
        "%x = u4[64]{0} all-reduce(%y), replica_groups={{0,1}}")
    assert r["counts"]["all-reduce"] == 1       # schedule still visible
    assert r["total_bytes"] == 0.0              # but no sizing guess


def test_count_fusions():
    hlo = """
      %fused_computation { %p0 = f32[4]{0} parameter(0) }
      %f.1 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation
      %f.2 = (f32[4]{0}, f32[4]{0}) fusion(%a, %b), kind=kOutput
      %add = f32[4]{0} add(%a, %b)
    """
    assert count_fusions(hlo) == 2
    assert count_fusions("%x = f32[4]{0} add(%a, %b)") == 0


def test_collective_parser_ignores_done_and_noncollectives():
    r = collective_bytes_from_hlo(
        "%x = f32[4]{0} all-reduce-done(%y), replica_groups={{0,1}}")
    assert r["total_bytes"] == 0
    r = collective_bytes_from_hlo("%x = f32[4]{0} reduce(%y)")
    assert r["total_bytes"] == 0
    # group of 1 (degenerate) moves nothing
    r = collective_bytes_from_hlo(
        "%x = f32[4]{0} all-reduce(%y), replica_groups={{0}}")
    assert r["total_bytes"] == 0


def test_roofline_terms_and_bottleneck():
    rec = {
        "cell": "llama3.2-1b/train_4k", "mesh": "pod16x16", "ok": True,
        "analysis": {
            "flops": 1.97e12,                 # exactly 10 ms of compute
            "bytes_accessed": 819e9 * 0.02,   # 20 ms of HBM
            "collectives": {"total_bytes": 50e9 * 0.001},
            "corrected": {},
        },
    }
    r = analyze_record(rec)
    assert abs(r["compute_s"] - 0.01) < 1e-9
    assert abs(r["memory_s"] - 0.02) < 1e-9
    assert abs(r["collective_s"] - 0.001) < 1e-9
    assert r["bottleneck"] == "memory"
    assert r["model_over_hlo"] is not None


def test_model_flops_formulas():
    # train: 6·N_active·tokens; decode: 2·N_active·tokens
    assert model_flops("llama3.2-1b", "train_4k") == \
        6.0 * 1.24e9 * 4096 * 256
    assert model_flops("kimi-k2-1t-a32b", "decode_32k") == \
        2.0 * 32.6e9 * 128
    assert model_flops("unknown-arch", "train_4k") is None


# --------------------------------------------------------------------------
# hserve serving steps: abstract-table lowering + collective analysis
# (the dryrun --he serving cells, exercised in-process at test params —
# launch.dryrun itself is never imported here, its import sets XLA_FLAGS)
# --------------------------------------------------------------------------

def _serving_lowered(op: str, batch: int = 2, logq=None):
    from repro.core.params import test_params
    from repro.launch.cells import lower_he_serving_cell

    params = test_params(logN=4, beta_bits=32)
    mesh = make_mesh((1, 1), ("data", "model"))
    return lower_he_serving_cell(op, batch, mesh, logq=logq, params=params)


def _full_op_table():
    from repro.launch.cells import HE_SERVING_OPS
    return HE_SERVING_OPS


@pytest.mark.parametrize("op", _full_op_table())
def test_serving_steps_lower_with_abstract_tables(op):
    """EVERY op in the served table (`analysis.dataflow.OPS` — mul, add,
    sub, rotate, conjugate, slot_sum, rescale, mod_down, mul_plain,
    add_plain) lowers + compiles from he_table_specs alone and produces a
    full analysis record, so no served op can dodge dry-run/shardlint
    coverage."""
    from repro.launch.hlo_analysis import analyze_compiled

    lowered = _serving_lowered(op)
    rec = analyze_compiled(lowered, lowered.compile(), 0.0)
    assert set(rec) >= {"flops", "bytes_accessed", "collectives",
                        "memory", "fusions", "compile_seconds"}, op
    assert rec["collectives"]["counts"] is not None, op
    # single-device mesh: nothing should hit the wire
    assert rec["collectives"]["total_bytes"] == 0.0, op


def test_serving_op_table_matches_dataflow_and_levels_filter():
    """The lowering table is generated FROM the analysis dataflow op set
    (a newly served op cannot dodge coverage), and level filtering only
    trims the level-consuming ops at the chain bottom and the
    level-raising mod_raise at the chain top."""
    from repro.analysis.dataflow import OPS, PLAIN_OPS
    from repro.core.params import test_params
    from repro.launch.cells import HE_SERVING_OPS, serving_op_levels

    assert set(HE_SERVING_OPS) == set(OPS)
    assert set(PLAIN_OPS) <= set(HE_SERVING_OPS)
    params = test_params(logN=4, beta_bits=32)
    levels = (params.logQ, 3 * params.logp, params.logp)
    for op in HE_SERVING_OPS:
        got = serving_op_levels(op, levels, params)
        if op in ("rescale", "mod_down"):
            assert got == [lq for lq in levels if lq >= 2 * params.logp], op
        elif op == "mod_raise":
            assert got == [lq for lq in levels
                           if lq + params.logp <= params.logQ], op
        else:
            assert got == list(levels), op
    with pytest.raises(ValueError, match="unknown serving op"):
        _serving_lowered("bootstrap")


def test_plain_ops_have_no_keyswitch_collectives_and_cost_less():
    """The plaintext-operand ops' acceptance claim, checked on real HLO:
    neither carries ANY collective bytes (no region-2 key switch —
    rotate, by contrast, pays the full key-switch chain), add_plain is a
    bare limb add (orders of magnitude below the NTT ops), and
    mul_plain's region-1-only FLOPs stay well under rotate's region-2
    pipeline."""
    from repro.launch.hlo_analysis import (
        analyze_compiled, collective_bytes_from_hlo,
    )

    recs = {}
    for op in ("rotate", "mul_plain", "add_plain"):
        lowered = _serving_lowered(op)
        recs[op] = analyze_compiled(lowered, lowered.compile(), 0.0)
        # the parser on the pre-partitioning HLO text as well
        assert collective_bytes_from_hlo(
            lowered.as_text())["total_bytes"] == 0.0 \
            or op == "rotate", op
    for op in ("mul_plain", "add_plain"):
        assert recs[op]["collectives"]["total_bytes"] == 0.0, op
        assert not any(recs[op]["collectives"]["counts"].values()), op
    if recs["rotate"]["flops"] and recs["mul_plain"]["flops"]:
        assert recs["mul_plain"]["flops"] < recs["rotate"]["flops"]
    if recs["mul_plain"]["flops"] and recs["add_plain"]["flops"]:
        assert recs["add_plain"]["flops"] < recs["mul_plain"]["flops"] / 10


def test_rescale_step_has_no_collectives_and_fewer_flops():
    """Rescale is a pure limb shift — no NTT, no key switch: its HLO
    must contain zero collectives and cost far less than a rotate (the
    docs/ARCHITECTURE.md dataflow-table claim, checked on real HLO)."""
    from repro.launch.hlo_analysis import (
        analyze_compiled, collective_bytes_from_hlo,
    )

    rot = _serving_lowered("rotate")
    res = _serving_lowered("rescale")
    rec_rot = analyze_compiled(rot, rot.compile(), 0.0)
    rec_res = analyze_compiled(res, res.compile(), 0.0)
    # collective parser on the pre-partitioning HLO text as well
    assert collective_bytes_from_hlo(res.as_text())["total_bytes"] == 0.0
    if rec_rot["flops"] and rec_res["flops"]:
        assert rec_res["flops"] < rec_rot["flops"] / 10

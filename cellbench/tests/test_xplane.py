"""The trace reductions on hand-made event lists."""
import pytest

from cellbench import xplane

MS = 1e6


def plane():
    ops = [
        ("%fusion.1 = bf16[64,14336]{1,0} fusion(...)", 0 * MS, 10 * MS),
        ("%paged_attention_wide.65 = bf16[64,8,4,128]{3,2} custom-call(",
         10 * MS, 5 * MS),
        # nested inside the kernel's interval: not counted twice
        ("%copy.3 = bf16[8]{0} copy(x)", 11 * MS, 2 * MS),
        ("%paged_attention_wide.66 = bf16[64,8,4,128]{3,2} custom-call(",
         40 * MS, 5 * MS),
        ("%fusion.2 = bf16[64,14336]{1,0} fusion(...)", 45 * MS, 15 * MS),
    ]
    mods = [("jit__mixed_step(123)", 0 * MS, 15 * MS),
            ("jit__threefry_split(9)", 30 * MS, 0.001 * MS),
            ("jit__mixed_step(123)", 40 * MS, 20 * MS),
            ("jit__decode_plain_core(7)", 80 * MS, 20 * MS)]
    return {xplane.OPS_LINE: ops, xplane.MODULES_LINE: mods}


def test_busy_is_a_union_and_idle_is_the_rest():
    busy, window = xplane.busy_and_window(plane())
    assert busy == pytest.approx(0.035)   # 0-15 and 40-60 ms
    assert window == pytest.approx(0.100)  # first start to last end
    b, w = xplane.device_busy({"/device:TPU:0": plane(),
                               "/device:TPU:1": plane()})
    assert (b, w) == (pytest.approx(0.035), pytest.approx(0.100))
    assert xplane.device_busy({}) == (0.0, 0.0)


def test_kernel_time_families_and_programs():
    p = plane()
    ops = p[xplane.OPS_LINE]
    assert xplane.matching_seconds(ops, ["paged_attention"]) == \
        pytest.approx(0.010)
    assert xplane.op_family(ops[1][0]) == \
        "paged_attention_wide bf16[64,8,4,128]"
    assert xplane.op_family("%fusion.7 = (bf16[2,4], f32[2]) fusion(") \
        == "fusion bf16[2,4]"
    top = xplane.top_ops(p, 2)
    assert top[0][0] == "fusion bf16[64,14336]"
    assert top[0][1] == pytest.approx(0.025)
    assert top[1] == ["paged_attention_wide bf16[64,8,4,128]",
                      pytest.approx(0.010)]
    assert xplane.module_median_ms(p, "_mixed_step") == pytest.approx(17.5)
    assert xplane.module_median_ms(p, "absent") is None
    gaps = xplane.idle_gaps(p, 2)
    assert gaps[0][0].startswith("jit__decode_plain_core")
    assert gaps[0][1] == pytest.approx(0.020)


def test_collective_overlap_is_a_union_too():
    # what a collective metric will need: time in matching ops during
    # which nothing else runs = union(all) - union(others)
    compute = [("%fusion.1 = f32[8] fusion(", 0.0, 10 * MS)]
    coll = [("%all-gather.1 = f32[8] all-gather(", 5 * MS, 10 * MS)]
    exposed = xplane.union_seconds(compute + coll) - \
        xplane.union_seconds(compute)
    assert exposed == pytest.approx(0.005)

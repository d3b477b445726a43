"""Idle time put down gap by gap, on hand-made events, and both trace
readers once on a real `.xplane.pb` captured here on the CPU."""
import glob
import os

import pytest

from cellbench import hostplane, xplane

MS = 1e6


def mod(start_ms, dur_ms, name="jit__mixed_step(1)"):
    return (name, start_ms * MS, dur_ms * MS, None)


def ph(phase, start_ms, dur_ms, it=1):
    return ("sched/" + phase, start_ms * MS, dur_ms * MS, it)


def test_a_gap_wholly_in_commit():
    by = hostplane.idle_by_group(
        [mod(0, 10), mod(14, 10)],
        [ph("device", 2, 9), ph("commit", 11, 5)])
    # 10-11 ms is the end of `device` after its program, 11-14 `commit`
    assert by == {"commit": 4 * MS, "launch": 0.0, "plan": 0.0,
                  "unnamed": 0.0}


def test_a_gap_split_over_commit_and_launch():
    by = hostplane.idle_by_group(
        [mod(0, 10), mod(16, 10)],
        [ph("commit", 10, 2), ph("launch", 12, 3), ph("epilogue", 15, 4)])
    assert by["commit"] == pytest.approx(3 * MS)   # commit 2, epilogue 1
    assert by["launch"] == pytest.approx(3 * MS)
    assert by["plan"] == by["unnamed"] == 0.0


def test_build_under_a_running_program_counts_nothing():
    # 9 ms of build, 8 of them while the first program runs: only the
    # millisecond that outlasted it is idle time
    by = hostplane.idle_by_group(
        [mod(0, 10), mod(11, 10)], [ph("build", 2, 9)])
    assert by["plan"] == pytest.approx(1 * MS)
    assert by["commit"] == by["launch"] == by["unnamed"] == 0.0
    by = hostplane.idle_by_group([mod(0, 30)], [ph("build", 2, 9)])
    assert sum(by.values()) == 0.0


def test_an_uncovered_gap_is_unnamed():
    by = hostplane.idle_by_group(
        [mod(0, 10), mod(20, 10)],
        [ph("commit", 10, 2, it=1), ph("sweep", 18, 4, it=2)])
    # 12-18 ms: between two steps, no phase open
    assert by["unnamed"] == pytest.approx(6 * MS)
    assert by["commit"] == pytest.approx(2 * MS)
    assert by["plan"] == pytest.approx(2 * MS)
    # `sched/iteration` is no phase: it names nothing
    by = hostplane.idle_by_group(
        [mod(0, 10), mod(20, 10)], [ph("iteration", 0, 30)])
    assert by["unnamed"] == pytest.approx(10 * MS)


def trace():
    mods = [mod(0, 10), mod(10.001, 0.001, "jit__threefry_split(9)"),
            mod(16, 12), mod(40, 10)]
    ops = [("%fusion.1 = bf16[8,64,14336]{2,1,0} fusion(", 0 * MS, 6 * MS,
            "jit(_mixed_step)/decode_rounds/moe_experts/ecd,edf->ecf/"
            "dot_general:"),
           ("%fusion.2 = bf16[8,256,14336]{2,1,0} fusion(", 6 * MS, 3 * MS,
            "jit(_mixed_step)/prefill_group/moe_experts/ecd,edf->ecf/"
            "dot_general:"),
           # inside the one above: not counted twice
           ("%copy.3 = bf16[8]{0} copy(", 7 * MS, 1 * MS,
            "jit(_mixed_step)/prefill_group/moe_experts/mul:"),
           ("%paged_attention_wide.5 = bf16[64,8,4,128] custom-call(",
            16 * MS, 11 * MS, "jit(_mixed_step)/decode_rounds/attn/"
            "pallas_call:"),
           ("%fusion.9 = f32[64]{0} fusion(", 40 * MS, 10 * MS, "")]
    sched = [ph("iteration", 1, 15, it=1), ph("device", 1, 10),
             ph("commit", 11, 2), ph("launch", 13, 2.5),
             ph("epilogue", 15.5, 0.5),
             ph("iteration", 20, 21, it=2), ph("sweep", 20, 1, it=2),
             ph("build", 21, 5, it=2), ph("device", 26, 3.5, it=2),
             ph("commit", 29.5, 6.5, it=2), ph("launch", 36, 5, it=2)]
    return {"sched": {"scheduler/7": sched,
                      "other/9": [ph("sweep", 0, 50, it=9)]},
            "devices": {"/device:TPU:0": {hostplane.MODULES_LINE: mods,
                                          hostplane.OPS_LINE: ops}}}


def test_the_four_shares_add_up_to_the_idle_time_between_programs():
    tr = trace()
    assert [e[3] for e in hostplane.iterations(tr)] == [1, 2]
    assert all(e[0] != "sched/iteration" for e in hostplane.phases(tr))
    shares = hostplane.idle_shares(tr)
    plane = hostplane.first_device(tr)
    span = hostplane.span_ns(plane)
    assert span == pytest.approx(50 * MS)
    between = sum(b - a for a, b in hostplane.gaps(
        plane[hostplane.MODULES_LINE]))
    four = sum(shares[g] for g in ("commit", "launch", "plan", "unnamed"))
    assert four == pytest.approx(100.0 * between / span)
    # and with the idle time inside programs, to `device_idle_share`
    busy, window = xplane.busy_and_window(
        {k: [e[:3] for e in v] for k, v in plane.items()})
    assert window * 1e9 == pytest.approx(span)
    assert four + shares["in_program"] == pytest.approx(
        100.0 * (1.0 - busy / window))
    # 10-16 ms: device 1 (its end), commit 2, launch 2.5, epilogue 0.5;
    # 28-40 ms: device 1.5, commit 6.5, launch 4
    assert shares["commit"] == pytest.approx(100 * 11.499 / 50, abs=0.01)
    assert shares["launch"] == pytest.approx(100 * 6.5 / 50)
    assert shares["plan"] == shares["unnamed"] == 0.0
    assert hostplane.idle_shares({"sched": {}, "devices":
                                  tr["devices"]}) is None


def test_scope_shares_are_unions_over_busy_time():
    tr = trace()
    busy = 9 + 11 + 10  # ms: 0-9, 16-27, 40-50
    assert hostplane.scope_share(
        tr, "/decode_rounds/", "/moe_experts/") == pytest.approx(
            100 * 6 / busy)
    assert hostplane.scope_share(
        tr, "/prefill_group/", "/moe_experts/") == pytest.approx(
            100 * 3 / busy)
    # a program without the scopes (the parent commit): nothing to read
    for ops in tr["devices"]["/device:TPU:0"].values():
        ops[:] = [e[:3] + (None,) for e in ops]
    assert hostplane.scope_share(tr, "/decode_rounds/",
                                 "/moe_experts/") is None


@pytest.fixture(scope="module")
def real_xplane(tmp_path_factory):
    """A trace captured here: a jitted function under named scopes, in
    `sched/*` annotations with an index, the way the server emits them."""
    import jax
    import jax.numpy as jnp

    def f(x, w):
        with jax.named_scope("decode_rounds"):
            with jax.named_scope("moe_experts"):
                return jnp.einsum("ecd,edf->ecf", x, w)

    fj = jax.jit(f)
    x, w = jnp.ones((2, 8, 16)), jnp.ones((2, 16, 32))
    fj(x, w).block_until_ready()
    logdir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(logdir))
    try:
        for i in range(3):
            with jax.profiler.TraceAnnotation("sched/iteration",
                                              iteration=i):
                with jax.profiler.TraceAnnotation("sched/launch",
                                                  iteration=i):
                    y = fj(x, w)
                with jax.profiler.TraceAnnotation("sched/device",
                                                  iteration=i):
                    y.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = xplane.find_xplane(str(logdir))
    assert path and glob.glob(os.path.join(str(logdir), "plugins",
                                           "profile", "*", "*.xplane.pb"))
    return path


def test_both_readers_on_a_real_file(real_xplane):
    """The wire-format reader against `jax.profiler.ProfileData` on the
    same file: the `sched/*` events, their clock and their index agree.
    A CPU trace has no `/device:TPU:` plane: both readers say so and the
    reductions find nothing to read, which is what a reader returns on
    a program or a device that lacks what it looks for."""
    from jax.profiler import ProfileData
    tr = hostplane.load(real_xplane)
    want = []
    for plane in ProfileData.from_file(real_xplane).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                want += [(e.name, e.start_ns, e.duration_ns,
                          dict(e.stats).get("iteration"))
                         for e in line.events if e.name.startswith("sched/")]
    got = [e for line in tr["sched"].values() for e in line]
    assert len(got) == len(want) == 9
    for g, w in zip(sorted(got, key=lambda e: (e[1], -e[2])),
                    sorted(want, key=lambda e: (e[1], -e[2]))):
        assert g[0] == w[0] and g[3] == w[3]
        assert g[1] == pytest.approx(w[1], abs=1.0)   # ns: one truncates
        assert g[2] == pytest.approx(w[2], abs=1.0)
    assert [e[3] for e in hostplane.iterations(tr)] == [0, 1, 2]
    assert [e[0] for e in hostplane.phases(tr)] == [
        "sched/launch", "sched/device"] * 3
    assert tr["devices"] == {} and xplane.load(real_xplane) == {}
    assert hostplane.idle_shares(tr) is None
    assert hostplane.scope_share(tr, "/decode_rounds/") is None
    assert xplane.device_busy(xplane.load(real_xplane)) == (0.0, 0.0)

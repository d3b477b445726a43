"""Benchmark harness — runs on the real TPU chip.

Times the full jitted training step (fwd+bwd+optimizer) of a ~330M-param
dense decoder LM in bfloat16 and prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

The reference (view-sonic/Cloud-Server @ v0) publishes no numbers
(BASELINE.md: empty working tree), so `vs_baseline` is computed against the
previous round's own result (BENCH_r01.json: 26,249.5 tok/s on this same
config) — round-over-round regression tracking rather than a constant 1.0.

Config notes (measured on TPU v5e, this repo):
  * attention_impl="flash" + remat="dots" (with the flash residuals saved
    via checkpoint_name): 312 -> ~229 ms/step vs the r1 XLA-attention path.
  * the S=2048 extra compares the pallas flash kernel against XLA dense
    attention at long sequence in a training-style fwd+bwd.
  * r2 sweep results at this config (kept for provenance, all slower or
    invalid): vocab_chunk 4k/8k ~+4%, remat="attn" ~+4%, remat="none"
    fails to compile even with flash, bf16 master params -5% but changes
    optimizer numerics. Step decomposition: fwd 62 ms, bwd ~145 ms,
    optimizer 18 ms (near bandwidth-bound: ~9 GB of f32 param/moment
    traffic).
  * r3 flash-backward sweep (all kept losing variants, see
    ops/flash_attention.py): blocks 512 + staged-dq single-recompute
    backward 236 ms, blocks 512 + two-pass 242 ms, vs 221 ms for the
    1024 single-block fused backward — the block-level causal skip's
    FLOP saving loses to dq-staging HBM traffic / second recompute at
    this size (the backward is bandwidth-bound). Defaults unchanged.
  * r3 decode-attention finding (careful differential timing,
    benchmarks/decode_attention_bench.py): XLA's dense decode attention
    runs at ~790 GB/s effective at B=8/S=1024/W=1 — essentially the HBM
    roofline — so no kernel can beat it at full-length contexts; the
    paged kernel's value is block-table indirection + length-bounded
    reads (ragged contexts) at near-roofline, not a speedup at XLA's
    best shape.
  * r4 MFU sweep (benchmarks/mfu_sweep.py, matmul_roofline.py) — all
    measured LOSERS at the unchanged 330M config, baseline 215.9 ms:
    vocab_chunk 4096/8192 -> 223.7/221.6 ms (reconfirms r2);
    scan_layers_unroll 2/4 -> 240.9/254.0 ms; remat="attn" -> 226.5 ms;
    remat="none" did not compile in r4, with flash AND with xla
    attention (not re-tried on the chip since) — so the policy most
    likely to cut the backward is unmeasured, not flash-specific.
    Roofline context: the model's own matmul shapes sustain 193-236
    TF/s in isolation (within ~15% of wide-matmul rates on this chip),
    so the plateau is inter-matmul overhead (attention kernel, norms,
    saved-activation traffic, scheduling), not matmul geometry —
    without a profiler trace of the step, the remaining levers are
    hand-fused pallas (qkv+rope+write, CE) whose plausible wins are
    single-digit ms each.
  * r5 fused-CE kernel (ops/fused_ce.py, ce_impl="pallas" — now the
    bench config): the r4-nominated CE lever, built and measured.
    Decomposition first (benchmarks/step_decomposition.py): full step
    220.0 = hidden fwd+bwd 189.1 + CE ~16.5 + optimizer 14.4; the
    dense CE pays f32 d_logits matmul passes + ~4 GB of logits round
    trips. Kernel A/B at the bench config
    (benchmarks/fused_ce_bench.py): CE fwd+bwd 23.5 -> 18.7 ms, FULL
    STEP 220.5 -> 214.3 ms (-2.8%) — the first move in the ~0.377 MFU
    plateau in three rounds (-> ~0.390). Variants measured: two-kernel
    bwd (dx + dW each recomputing logits) 22.0 ms; emitted-d single
    recompute + XLA dW matmul 18.7 ms (kept); row tiles 512 19.7 ms
    (256 kept); bwd vocab tiles 640 under the default 16 MB scoped
    vmem 24.1 ms (3200 with vmem_limit_bytes=100MB kept).
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

from cloud_server_tpu.utils.bench_helpers import make_prompt_fn, pct, top_up
from cloud_server_tpu.utils.metrics import device_peaks
from cloud_server_tpu.utils.platform import (
    enable_compile_cache, require_tpu)


def _baseline_tokens_per_sec() -> tuple[str, float]:
    """(round_tag, tokens/s) of the latest BENCH_r*.json present — so
    vs_baseline is a round-over-round ratio and a regression shows up as
    < 1.0 at a glance (r3's ratio-to-r1 hid a 0.6% regression vs r2).
    The tag rides in the output so a reader can tell WHICH round the
    ratio divides by (if this round's own file has already been saved
    when bench re-runs, the ratio is vs itself ~= 1.0 and the tag says
    so). Falls back to 1:1 if no prior bench file exists."""
    import glob
    here = os.path.dirname(os.path.abspath(__file__))
    paths = sorted(glob.glob(os.path.join(here, "BENCH_r*.json")))
    for path in reversed(paths):
        try:
            with open(path) as f:
                value = float(json.load(f)["parsed"]["value"])
            tag = os.path.basename(path)[len("BENCH_"):-len(".json")]
            return tag, value
        except (OSError, KeyError, ValueError, TypeError):
            continue
    return "none", 0.0


def diff_time_scan_multi(make_fn, args, n1: int, n2: int, *,
                         reps: int = 2, n_meas: int = 1) -> list[float]:
    """Per-iteration seconds via the two-length differential: the
    fixed dispatch+sync cost of a call cancels in
    (t(n2) - t(n1)) / (n2 - n1). Best-of-`reps` per length; pick n2 so
    (n2 - n1) x per-iter >> the fixed cost's variance.

    Returns `n_meas` INDEPENDENT differential estimates from ONE pair of
    compiled fns (the repeats that establish run-to-run spread must not
    pay the compilation again). r3 learned why repeats matter: a single differential
    produced 12.0 us for a read that the HBM roofline bounds at ~40 us."""
    fns = {}
    for n in (n1, n2):
        fn = jax.jit(make_fn(n))
        jax.block_until_ready(fn(*args))  # compile + warm
        fns[n] = fn
    out = []
    for _ in range(n_meas):
        best = {}
        for n in (n1, n2):
            b = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fns[n](*args))
                b = min(b, time.perf_counter() - t0)
            best[n] = b
        out.append((best[n2] - best[n1]) / (n2 - n1))
    return out


def diff_time_scan(make_fn, args, n1: int, n2: int, reps: int = 2) -> float:
    return diff_time_scan_multi(make_fn, args, n1, n2, reps=reps)[0]


def train_bench():
    from cloud_server_tpu.config import MeshConfig, ModelConfig, TrainConfig
    from cloud_server_tpu.parallel.mesh import make_mesh
    from cloud_server_tpu.training import init_train_state, make_train_step

    model_cfg = ModelConfig(
        vocab_size=32000, embed_dim=1024, num_layers=16, num_heads=16,
        num_kv_heads=16, head_dim=64, mlp_dim=4096, max_seq_len=1024,
        dtype="bfloat16", param_dtype="float32", remat="dots",
        attention_impl="flash", ce_impl="pallas")
    batch, seq = 8, 1024
    train_cfg = TrainConfig(batch_size=batch, seq_len=seq, warmup_steps=10,
                            total_steps=100)

    mesh = make_mesh(MeshConfig())  # single chip
    state = init_train_state(model_cfg, train_cfg, mesh, jax.random.key(0))
    step, batch_sharding = make_train_step(model_cfg, train_cfg, mesh)
    tokens = jax.device_put(
        jax.random.randint(jax.random.key(1), (batch, seq), 0,
                           model_cfg.vocab_size), batch_sharding)
    data = {"tokens": tokens}

    for _ in range(3):
        state, metrics = step(state, data)
    jax.block_until_ready((state, metrics))

    n_steps = 20
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, metrics = step(state, data)
    # the optimizer update may still be in flight after the loss is
    # ready: wait for the state too
    jax.block_until_ready((state, metrics))
    dt = time.perf_counter() - t0
    loss_val = float(metrics["loss"])
    if loss_val != loss_val:
        raise SystemExit("bench invalid: loss is NaN")

    tokens_per_sec = batch * seq * n_steps / dt

    # Rough MFU: 6 * non-embedding params * tokens for fwd+bwd, vs the
    # device's published bf16 peak (utils.metrics.DEVICE_PEAKS).
    n_layer_params = model_cfg.num_layers * (
        4 * model_cfg.embed_dim * model_cfg.num_heads * model_cfg.head_dim
        + 3 * model_cfg.embed_dim * model_cfg.mlp_dim)
    n_embed = 2 * model_cfg.vocab_size * model_cfg.embed_dim
    flops_per_token = 6 * (n_layer_params + n_embed)
    mfu = flops_per_token * tokens_per_sec / device_peaks().bf16_flops

    return {
        "tokens_per_sec": tokens_per_sec,
        "step_time_ms": 1000 * dt / n_steps,
        "approx_mfu": mfu,
    }


def longseq_attention_bench():
    """Training-style fwd+bwd through a 4-layer stack at S=2048:
    pallas flash kernel vs XLA dense attention."""
    import dataclasses

    from cloud_server_tpu.config import ModelConfig
    from cloud_server_tpu.models import transformer

    base = ModelConfig(
        vocab_size=8192, embed_dim=1024, num_layers=4, num_heads=16,
        num_kv_heads=16, head_dim=64, mlp_dim=4096, max_seq_len=2048,
        dtype="bfloat16", param_dtype="float32", remat="dots")
    tokens = jax.random.randint(jax.random.key(2), (4, 2048), 0,
                                base.vocab_size)
    batch = {"tokens": tokens}

    out = {}
    for impl in ("flash", "xla"):
        cfg = dataclasses.replace(base, attention_impl=impl)
        params = transformer.init_params(cfg, jax.random.key(0))

        @jax.jit
        def grad_fn(params, batch, cfg=cfg):
            def loss(p):
                l, _ = transformer.next_token_loss(p, batch, cfg)
                return l
            return jax.grad(loss)(params)

        g = grad_fn(params, batch)
        float(jax.tree.leaves(g)[0].reshape(-1)[0].astype(jnp.float32))
        n = 10
        t0 = time.perf_counter()
        for _ in range(n):
            g = grad_fn(params, batch)
        float(jax.tree.leaves(g)[0].reshape(-1)[0].astype(jnp.float32))
        out[impl] = 1000 * (time.perf_counter() - t0) / n
    return {"s2048_fwdbwd_flash_ms": out["flash"],
            "s2048_fwdbwd_xla_ms": out["xla"],
            "s2048_flash_speedup": out["xla"] / out["flash"]}


def serving_bench():
    """Steady-state continuous-batching decode on the 330M model: 8 slots
    x 1024 context on the paged server (ops.paged_attention kernel),
    bf16 and int8 KV, and in-server n-gram speculative decoding.

    Keys keep their r1/r2 names for round-over-round comparability:
    "pallas" rows mean the paged server + kernel.

    Every scheduler iteration pays one fixed dispatch+sync cost,
    amortised here over decode_chunk=32 rounds. Kernel-level numbers
    live in the attn8k/attn1k extras (differential timing, where that
    fixed cost cancels)."""
    import dataclasses

    import numpy as np  # noqa: F401 (prompt construction)

    from cloud_server_tpu.config import InferConfig, ModelConfig
    from cloud_server_tpu.inference.paged_server import PagedInferenceServer
    from cloud_server_tpu.models import transformer

    base = ModelConfig(
        vocab_size=32000, embed_dim=1024, num_layers=16, num_heads=16,
        num_kv_heads=16, head_dim=64, mlp_dim=4096, max_seq_len=1024,
        dtype="bfloat16", param_dtype="float32", remat="none")
    infer_cfg = InferConfig(max_decode_len=900, temperature=1.0,
                            eos_token_id=-1, pad_token_id=0)
    params_bf16 = transformer.init_params(base, jax.random.key(0))
    _rng = np.random.RandomState(7)
    plain_prompts = [[int(x) for x in _rng.randint(1, 30000, size=64)]
                     for _ in range(8)]
    # repetitive prompts: the n-gram speculative sweet spot (code/tables)
    rep_prompts = [([3, 17, 9, 4] * 16)[:64] for _ in range(8)]
    greedy = dataclasses.replace(infer_cfg, temperature=0.0)

    chunk = 32
    out = {}

    def run_paged(tag, params, kv, *, spec=0, prompts=plain_prompts,
                  icfg=None, sampling=None):
        cfg = dataclasses.replace(base, kv_cache_dtype=kv,
                                  decode_attention_impl="pallas")
        srv = PagedInferenceServer(
            params, cfg, icfg or infer_cfg, max_slots=8, max_context=1024,
            page_size=128, prefill_chunk=256, decode_chunk=chunk,
            spec_drafts=spec, prompt_buckets=[64, 128])
        for i, p in enumerate(prompts):
            srv.submit(p, max_new_tokens=880,
                       sampling=sampling(i) if sampling else None)
        for _ in range(3):
            srv.step()
        before = srv.tokens_emitted
        r0, c0 = srv.decode_rounds, srv.decode_tokens_committed
        t0 = time.perf_counter()
        for _ in range(8):
            srv.step()
        dt = time.perf_counter() - t0
        out[tag] = (srv.tokens_emitted - before) / dt
        print(f"[serving_bench] {tag}: {out[tag]:.1f}", flush=True)
        if spec:
            rounds = srv.decode_rounds - r0
            out[tag + "_accept"] = ((srv.decode_tokens_committed - c0)
                                    / max(rounds, 1))
        srv.stop()

    run_paged("decode_tok_s_pallas_bf16", params_bf16, "model")
    # A/B for the per-request-sampling hot path: SamplingParams(seed=i)
    # forces the SamplingRows decode dispatch with math identical to the
    # server default (temperature 1.0) — the tok/s delta vs the row
    # above IS the rows-mode overhead (r4 shipped the rows threading
    # with a correctness test but no on-chip timing)
    from cloud_server_tpu.inference.sampling import SamplingParams
    run_paged("decode_tok_s_pallas_rows_on", params_bf16, "model",
              sampling=lambda i: SamplingParams(seed=1000 + i))
    run_paged("decode_tok_s_pallas_bf16_kvint8", params_bf16, "int8")
    # speculative: greedy so acceptance reflects the model, not sampling
    run_paged("decode_tok_s_pallas_spec_repeat", params_bf16, "model",
              spec=3, prompts=rep_prompts, icfg=greedy)
    run_paged("decode_tok_s_pallas_spec_random", params_bf16, "model",
              spec=3, prompts=plain_prompts, icfg=greedy)
    # churn rides in this section (reuses the params already on device)
    # — guarded so a churn-time failure cannot void the headline
    # decode rows measured above
    try:
        out.update(_churn_scenario(params_bf16, base, infer_cfg))
    except Exception as exc:  # noqa: BLE001
        print(f"[serving_bench] churn skipped after error: {exc!r}",
              flush=True)
        out["churn_error"] = repr(exc)[:160]
    # speculation-under-churn A/B (same guard discipline)
    try:
        out.update(_spec_churn_bench(params_bf16, base, infer_cfg))
    except Exception as exc:  # noqa: BLE001
        print(f"[serving_bench] churn_spec skipped after error: "
              f"{exc!r}", flush=True)
        out["churn_spec_error"] = repr(exc)[:160]
    # multi-tenant QoS isolation A/B (same guard discipline)
    try:
        out.update(_qos_isolation_bench(params_bf16, base, infer_cfg))
    except Exception as exc:  # noqa: BLE001
        print(f"[serving_bench] qos_isolation skipped after error: "
              f"{exc!r}", flush=True)
        out["qos_isolation_error"] = repr(exc)[:160]
    # shared-prefix cache churn under a multi-tenant flood (same guard)
    try:
        out.update(_prefix_cache_churn_bench(params_bf16, base,
                                             infer_cfg))
    except Exception as exc:  # noqa: BLE001
        print(f"[serving_bench] prefix_cache_churn skipped after "
              f"error: {exc!r}", flush=True)
        out["prefix_cache_churn_error"] = repr(exc)[:160]
    # fleet fault recovery: kill one replica mid-flood (same guard)
    try:
        out.update(_fault_recovery_bench(params_bf16, base, infer_cfg))
    except Exception as exc:  # noqa: BLE001
        print(f"[serving_bench] fault_recovery skipped after error: "
              f"{exc!r}", flush=True)
        out["fault_recovery_error"] = repr(exc)[:160]
    # disaggregated prefill/decode fleet A/B — the headline
    # role-specialization measurement (same guard discipline)
    try:
        out.update(_disagg_bench(params_bf16, base, infer_cfg))
    except Exception as exc:  # noqa: BLE001
        print(f"[serving_bench] disagg_vs_colocated skipped after "
              f"error: {exc!r}", flush=True)
        out["disagg_vs_colocated_error"] = repr(exc)[:160]
    # anomaly watchdog + tail retention under an injected-fault flood
    # (same guard discipline)
    try:
        out.update(_anomaly_forensics_bench(params_bf16, base,
                                            infer_cfg))
    except Exception as exc:  # noqa: BLE001
        print(f"[serving_bench] anomaly_forensics skipped after "
              f"error: {exc!r}", flush=True)
        out["anomaly_forensics_error"] = repr(exc)[:160]
    # SLO-burn autoscaler vs static fleet on the diurnal-burst scenario
    # (same guard discipline)
    try:
        out.update(_slo_autoscale_bench(params_bf16, base, infer_cfg))
    except Exception as exc:  # noqa: BLE001
        print(f"[serving_bench] slo_autoscale skipped after "
              f"error: {exc!r}", flush=True)
        out["slo_autoscale_error"] = repr(exc)[:160]
    return out


def _fault_recovery_bench(params, base, infer_cfg):
    """Fleet fault recovery A/B (docs/serving.md "Fault tolerance"):
    a 2-replica router floods 16 requests; the injected arm arms a
    deterministic dispatch fault on replica 0 a few iterations in —
    its scheduler crashes exactly like a poisoned device program —
    and the run reports how the failure-domain layer absorbed it:

      * `fault_recovery_time_to_breaker_open_ms` — injected fault ->
        replica-0 breaker open (placement stops routing there);
      * `fault_recovery_retry_success_rate` — zero-token failed
        requests resubmitted to replica 1 that completed normally
        (the safe-retry rule);
      * `fault_recovery_migration_success_rate`, `..._migration_ms_p50`
        and `..._tokens_salvaged_frac` — the mid-stream kills: requests
        that had already streamed tokens are LIVE-MIGRATED (host state
        salvaged, resumed token-exact on replica 1) instead of failing
        fast; salvaged-frac is the share of the migrated requests'
        decode budget carried over rather than regenerated;
      * `fault_recovery_{baseline,injected}_completed_frac`,
        `..._slo_ttft` and `..._slo_itl` — the client-visible blast
        radius vs the uninjected control at identical load.

    Both arms run twice (untimed compile warm-up, then measured),
    like the churn benches."""
    import dataclasses

    import numpy as np

    from cloud_server_tpu.inference.faults import FaultPlan
    from cloud_server_tpu.inference.paged_server import PagedInferenceServer
    from cloud_server_tpu.inference.router import ReplicatedRouter

    cfg = dataclasses.replace(base, decode_attention_impl="pallas")
    slo_cfg = {"windows_s": [300],
               "classes": {"default": {"objective": 0.99, "ttft_s": 5.0,
                                       "itl_s": 2.0, "e2e_s": 600.0}}}
    max_new = 96

    def scenario(inject: bool):
        fp = FaultPlan() if inject else None

        def mk(faults):
            return PagedInferenceServer(
                params, cfg, infer_cfg, max_slots=8, max_context=1024,
                page_size=128, prefill_chunk=256, decode_chunk=8,
                prompt_buckets=[64, 256], slo=slo_cfg, faults=faults)

        router = ReplicatedRouter([mk(fp), mk(None)],
                                  breaker_threshold=3,
                                  breaker_reset_s=600.0)
        rng = np.random.RandomState(0)
        reqs = [router.submit([int(x) for x in
                               rng.randint(1, 30000, size=64)],
                              max_new_tokens=max_new)
                for _ in range(16)]
        for _ in range(4):
            router.step()
        t_fault = t_open = None
        if inject:
            fp.arm("dispatch", count=1)
            t_fault = time.perf_counter()
        deadline = time.perf_counter() + 300
        while (not all(r.done for r in reqs)
               and time.perf_counter() < deadline):
            router.step()
            if (inject and t_open is None
                    and router.breaker_states()[0]["state"] == "open"):
                t_open = time.perf_counter()
        ok = sum(1 for r in reqs
                 if r.done
                 and not (r.finish_reason or "").startswith("error"))
        rep = router.slo_report()
        mets = rep["classes"]["default"]["metrics"]

        def attainment(name):
            a = mets.get(name, {}).get("lifetime", {}).get("attainment")
            return 1.0 if a is None else a

        snap = router.metrics_snapshot()
        res = {"completed_frac": ok / len(reqs),
               "slo_ttft": attainment("ttft"),
               "slo_itl": attainment("itl")}
        if inject:
            res["time_to_breaker_open_ms"] = (
                -1.0 if t_open is None else (t_open - t_fault) * 1e3)
            retries = snap["cloud_server_router_retries_total"]["value"]
            succ = snap["cloud_server_router_retry_success_total"][
                "value"]
            res["retries"] = retries
            res["retry_success_rate"] = succ / max(retries, 1)
            # the mid-stream half of the kill: live migrations
            from cloud_server_tpu.utils.serving_metrics import \
                histogram_percentile
            mig = router.migration_stats()
            hist = snap.get("cloud_server_migration_ms")
            res["migrations"] = mig["out_started"]
            res["migration_success_rate"] = mig["success_rate"]
            res["migration_ms_p50"] = (
                histogram_percentile(hist, 0.50)
                if hist and hist.get("count") else -1.0)
            res["tokens_salvaged_frac"] = (
                mig["tokens_salvaged"]
                / max(mig["in_completed"] * max_new, 1))
        for r in reqs:
            r.cancel()
        router.run_until_idle()
        router.stop()
        return res

    out = {}
    for tag, inject in (("baseline", False), ("injected", True)):
        scenario(inject)  # warm-up: compile every shape
        res = scenario(inject)
        out[f"fault_recovery_{tag}_completed_frac"] = \
            res["completed_frac"]
        out[f"fault_recovery_{tag}_slo_ttft"] = res["slo_ttft"]
        out[f"fault_recovery_{tag}_slo_itl"] = res["slo_itl"]
        if inject:
            out["fault_recovery_time_to_breaker_open_ms"] = \
                res["time_to_breaker_open_ms"]
            out["fault_recovery_retries"] = res["retries"]
            out["fault_recovery_retry_success_rate"] = \
                res["retry_success_rate"]
            out["fault_recovery_migrations"] = res["migrations"]
            out["fault_recovery_migration_success_rate"] = \
                res["migration_success_rate"]
            out["fault_recovery_migration_ms_p50"] = \
                res["migration_ms_p50"]
            out["fault_recovery_tokens_salvaged_frac"] = \
                res["tokens_salvaged_frac"]
        print(f"[serving_bench] fault_recovery_{tag}: completed "
              f"{res['completed_frac']:.2f}, slo_ttft "
              f"{res['slo_ttft']:.3f}, slo_itl {res['slo_itl']:.3f}"
              + (f", breaker open in "
                 f"{res['time_to_breaker_open_ms']:.1f} ms, retry "
                 f"success {res['retry_success_rate']:.2f}, "
                 f"{res['migrations']} migrations (success "
                 f"{res['migration_success_rate']:.2f}, p50 "
                 f"{res['migration_ms_p50']:.1f} ms, salvaged "
                 f"{res['tokens_salvaged_frac']:.2f})"
                 if inject else ""), flush=True)
    return out


def _anomaly_forensics_bench(params, base, infer_cfg):
    """Anomaly watchdog + tail retention + forensic bundles under a
    churn flood with injected faults (docs/observability.md "Anomaly
    detection & forensics"), at trace_sample_rate=0.01 — the
    production-shaped sampling where head sampling alone would lose
    ~99% of broken requests' traces:

      * three incident rounds: each arms an `iteration_stall` fault
        (faults.py — the scheduler stalls mid-iteration) and lands a
        burst of deadline-doomed requests; `anomaly_detect_ms_p50` is
        the wall time from the burst to the watchdog's activation
        edge (`deadline_spike` latching), per round;
      * `bundle_on_anomaly` auto-captures a forensic bundle on each
        edge — asserted captured, carrying the covering flight
        window;
      * `churn_tail_traces_retained_frac` — the fraction of broken
        (deadline-expired) requests whose span trees survived at 1%
        head sampling via tail retention, asserted 1.0 with every
        retained tree gap-free (phase spans contiguous)."""
    import dataclasses

    from cloud_server_tpu.inference.faults import FaultPlan
    from cloud_server_tpu.inference.paged_server import PagedInferenceServer
    from cloud_server_tpu.inference.request_trace import PHASES

    cfg = dataclasses.replace(base, decode_attention_impl="pallas")
    icfg = dataclasses.replace(
        infer_cfg, trace_sample_rate=0.01, trace_tail_capacity=256,
        bundle_on_anomaly=True)
    # short windows so each incident round opens (and closes) its OWN
    # anomaly window: three distinct activation edges, three bundles
    anomaly_cfg = {"warmup": 0, "check_every": 1, "hold_s": 0.25,
                   "rules": {"deadline_spike":
                             {"count": 3, "window_s": 1.0}}}
    fp = FaultPlan()
    srv = PagedInferenceServer(
        params, cfg, icfg, max_slots=16, max_context=1024,
        page_size=128, prefill_chunk=256, decode_chunk=8,
        prompt_buckets=[64, 256],
        anomaly=anomaly_cfg, faults=fp)
    mk_prompt = make_prompt_fn(0)

    def feed():
        # the watchdog only observes BUSY iterations, so keep the
        # scheduler fed (window close needs observed time to pass) —
        # the shared top-up helper (utils/bench_helpers)
        top_up(srv, mk_prompt)

    # background churn flood at 1% head sampling; a few steps compile
    # every shape before the timed incident rounds
    flood = [srv.submit(mk_prompt(64), max_new_tokens=256)
             for _ in range(8)]
    for _ in range(4):
        srv.step()

    detect_ms = []
    detect_steps = []
    doomed = []
    fired_seen = 0
    for _ in range(3):
        fp.arm("iteration_stall", count=2, stall_ms=120.0)
        doomed_batch = [srv.submit(mk_prompt(64), max_new_tokens=64,
                                   deadline_s=1e-3) for _ in range(3)]
        doomed += doomed_batch
        t0 = time.perf_counter()
        steps = 0
        while time.perf_counter() - t0 < 60.0:
            feed()
            srv.step()
            steps += 1
            fired = sum(srv.anomaly_stats()["fired_total"].values())
            if fired > fired_seen:
                fired_seen = fired
                detect_ms.append((time.perf_counter() - t0) * 1e3)
                detect_steps.append(steps)
                break
        # step the open window shut before the next round (prune past
        # window_s, then hold_s of recovery)
        t_close = time.perf_counter()
        while (srv.anomaly_stats()["active"]
               and time.perf_counter() - t_close < 60.0):
            feed()
            srv.step()
    assert len(detect_ms) == 3, (
        f"watchdog latched {len(detect_ms)}/3 incident rounds")
    assert max(detect_steps) <= 50, (
        f"detection took {max(detect_steps)} iterations — not bounded")
    srv.run_until_idle()

    # injected fault really fired, bundles auto-captured on each edge
    # with the covering flight window
    fstats = srv.fault_stats()
    assert fstats["fired"]["iteration_stall"] >= 1, fstats["fired"]
    bundles = srv.debug_bundles()
    assert len(bundles) == 3, f"{len(bundles)} bundles for 3 edges"
    for b in bundles:
        assert b["trigger"] == "anomaly:deadline_spike"
        assert b["flight"], "bundle missing the covering flight window"
        assert b["anomaly"]["active"], "bundle missed the open window"

    # 100% of broken requests kept a gap-free tree at 1% head sampling
    # (lookup spans the head ring AND the tail ring — a doomed request
    # that happened to be head-sampled counts too)
    retained = 0
    for r in doomed:
        assert r.finish_reason == "deadline", r.finish_reason
        tree = srv.lookup_trace(r.request_id)
        if tree is None:
            continue
        retained += 1
        root = tree["root"]
        assert root["start"] == r.submit_time
        assert root["end"] is not None
        phases = [c for c in root["children"] if c["name"] in PHASES]
        assert phases[0]["start"] == root["start"]
        for a, b in zip(phases, phases[1:]):
            assert a["end"] == b["start"], \
                f"gap between {a['name']} and {b['name']}"
        assert phases[-1]["end"] == root["end"]
    frac = retained / len(doomed)
    assert frac == 1.0, (
        f"only {retained}/{len(doomed)} broken requests kept a tree")
    tstats = srv.tail_trace_stats()
    srv.stop()

    out = {"churn_tail_traces_retained_frac": frac,
           "anomaly_detect_ms_p50": pct(detect_ms, 0.50),
           "anomaly_detect_iters_max": max(detect_steps),
           "anomaly_bundles_captured": len(bundles),
           "anomaly_tail_retained_total":
               sum(tstats["retained_total"].values())}
    print(f"[serving_bench] anomaly_forensics: detect p50 "
          f"{out['anomaly_detect_ms_p50']:.1f} ms "
          f"(<= {out['anomaly_detect_iters_max']} iters), "
          f"{len(bundles)} bundles, tail retained frac {frac:.2f}",
          flush=True)
    return out


def _slo_autoscale_bench(params, base, infer_cfg):
    """SLO-burn autoscaler vs static fleet on the canonical
    quiet->burst->quiet diurnal scenario (scenarios.diurnal_burst),
    replayed by the scenario harness against two live fleets:

      * AUTOSCALED — starts at min_replicas=1 with a warm pool of
        spares; the SLOBurnAutoscaler polls fleet burn rates +
        pending depth and calls add_replica/remove_replica(migrate).
      * STATIC — a fixed fleet sized to the autoscaled arm's AVERAGE
        footprint rounded UP (ceil of chip-seconds / wall time), so
        the control spends at least as many chip-seconds. Equal-ish
        chip-seconds is the fairness control: the autoscaler's only
        edge is placing capacity WHEN the burst needs it.

    Reported: per-arm interactive attainment (worst lifetime metric
    from slo_report, removed replicas' trackers merged back in so
    scale-downs cannot drop history), chip-seconds, scale-up/down
    counts, and time-to-recover (burst start -> first scale-up).

    ASSERTS the acceptance bar: autoscaled interactive attainment >=
    static at chip-seconds <= static x 1.05, at least one scale-up
    AND one scale-down actually fired, and ZERO lost requests — every
    fired event completes (scale-down drains migrate, never drop)."""
    import dataclasses
    import math

    from cloud_server_tpu.inference.paged_server import PagedInferenceServer
    from cloud_server_tpu.inference.router import ReplicatedRouter
    from cloud_server_tpu.inference.slo import merge_reports
    from cloud_server_tpu.scenarios import (AutoscalerConfig, ReplayDriver,
                                            SLOBurnAutoscaler, TenantMix,
                                            diurnal_burst)

    cfg = dataclasses.replace(base, decode_attention_impl="pallas")
    qos_cfg = {"quantum": 64,
               "tenants": {
                   "inter": {"weight": 4.0, "priority": "interactive"},
                   "bulk": {"weight": 1.0, "priority": "batch"}}}
    # short windows so burn reacts within a ~40 s bench; targets sized
    # to pass when a request is served promptly and fail when it sits
    # behind an unscaled burst backlog
    slo_cfg = {"windows_s": [5, 15],
               "classes": {
                   "interactive": {"objective": 0.9, "ttft_s": 6.0,
                                   "queue_wait_s": 5.0, "itl_s": 3.0,
                                   "e2e_s": 60.0},
                   "batch": {"objective": 0.5, "ttft_s": 20.0,
                             "e2e_s": 120.0}}}
    phase_s = 12.0
    scenario = diurnal_burst(
        seed=0, duration_s=3 * phase_s, phase_s=phase_s,
        low_rps=0.2, high_rps=3.0,
        tenants=TenantMix({"inter": 3.0, "bulk": 1.0}))

    def mk():
        return PagedInferenceServer(
            params, cfg, infer_cfg, max_slots=8, max_context=1024,
            page_size=128, prefill_chunk=256, decode_chunk=8,
            prompt_buckets=[64, 256], qos=qos_cfg, slo=slo_cfg)

    def interactive_attainment(reports) -> float:
        rep = merge_reports(reports)
        centry = (rep or {}).get("classes", {}).get("interactive")
        if not centry:
            return 1.0
        vals = [m["lifetime"]["attainment"]
                for m in centry["metrics"].values()
                if m["lifetime"]["total"]]
        return min(vals) if vals else 1.0

    def run_arm(n_start, asc_pool):
        router = ReplicatedRouter([mk() for _ in range(n_start)])
        released = []
        asc = None
        if asc_pool is not None:
            spares = [mk() for _ in range(asc_pool)]
            asc = SLOBurnAutoscaler(
                router, spawn=lambda role: (spares.pop()
                                            if spares else None),
                release=released.append,
                config=AutoscalerConfig(
                    min_replicas=1, max_replicas=1 + asc_pool,
                    classes=("interactive", "batch", "default"),
                    up_fast_burn=1.5, up_slow_burn=1.0,
                    down_fast_burn=0.5, down_slow_burn=0.5,
                    pending_high=4.0, pending_low=1.0,
                    hold_s=4.0, poll_s=0.5, drain_timeout_s=60.0))
        drv = ReplayDriver(router, scenario.generate())
        state = {"t": time.monotonic(), "chips": 0.0, "poll": 0.0}
        t_start = state["t"]

        def pump():
            router.step()
            now = time.monotonic()
            state["chips"] += (len(router.attached_indices())
                               * (now - state["t"]))
            state["t"] = now
            if asc is not None and now - state["poll"] >= asc.cfg.poll_s:
                state["poll"] = now
                asc.step(now)

        res = drv.run(step=pump, timeout_s=600.0)
        router.run_until_idle()
        # the chip-second account covers the SERVING window only (both
        # arms pay for capacity held while requests could arrive/run);
        # freeze it here so the settle wait below is not billed
        t_end = time.monotonic()
        state["chips"] += (len(router.attached_indices())
                           * (t_end - state["t"]))
        state["t"] = t_end
        chips = state["chips"]
        elapsed = t_end - t_start
        # post-drain settle: let the quiet tail's scale-down land (the
        # burn windows need wall time to age the burst out)
        if asc is not None:
            t_settle = time.monotonic()
            while (len(router.attached_indices()) > 1
                   and time.monotonic() - t_settle < 30.0):
                pump()
                time.sleep(0.05)
        reports = [r.slo_report() for r in router.replicas
                   if hasattr(r, "slo_report")]
        # scale-downs detach trackers from the fleet report — merge the
        # released replicas back so attainment covers EVERY request
        reports += [r.slo_report() for r in released]
        att = interactive_attainment(reports)
        stats = asc.stats() if asc is not None else None
        events = list(asc.events) if asc is not None else []
        if asc is not None:
            asc.stop()
        for r in released:
            r.stop()
        router.stop()
        return {"res": res, "att": att, "chips": chips,
                "elapsed": elapsed, "stats": stats, "events": events,
                "t_start": t_start}

    # one throwaway replica warms the jit cache so neither arm pays
    # compile time inside its measured window
    warm = mk()
    mk_prompt = make_prompt_fn(0)
    warm.submit(mk_prompt(64), max_new_tokens=8, tenant="inter")
    warm.submit(mk_prompt(200), max_new_tokens=8, tenant="bulk")
    warm.run_until_idle()
    warm.stop()

    auto = run_arm(1, asc_pool=2)
    n_static = max(1, math.ceil(auto["chips"] / auto["elapsed"] - 1e-6))
    static = run_arm(n_static, asc_pool=None)

    ups = [e for e in auto["events"] if e.action == "up"]
    downs = [e for e in auto["events"] if e.action == "down"]
    recover_s = (max(0.0, ups[0].t - (auto["t_start"] + phase_s))
                 if ups else -1.0)
    out = {
        "slo_autoscale_auto_attainment": auto["att"],
        "slo_autoscale_static_attainment": static["att"],
        "slo_autoscale_auto_chip_s": auto["chips"],
        "slo_autoscale_static_chip_s": static["chips"],
        "slo_autoscale_static_replicas": n_static,
        "slo_autoscale_scale_ups": len(ups),
        "slo_autoscale_scale_downs": len(downs),
        "slo_autoscale_time_to_recover_s": recover_s,
        "slo_autoscale_lost_requests": (auto["res"]["failed"]
                                        + auto["res"]["outstanding"]
                                        + auto["res"]["rejected"]),
    }
    assert out["slo_autoscale_lost_requests"] == 0, (
        f"autoscaled arm lost requests: {auto['res']}")
    assert ups and downs, (
        f"autoscaler never cycled: {len(ups)} ups, {len(downs)} downs "
        f"(events: {[e.to_json() for e in auto['events']]})")
    assert auto["att"] >= static["att"], (
        f"autoscaled interactive attainment {auto['att']:.3f} < static "
        f"{static['att']:.3f} at n_static={n_static}")
    assert auto["chips"] <= static["chips"] * 1.05, (
        f"autoscaled burned more chip-seconds ({auto['chips']:.1f}) "
        f"than the static control ({static['chips']:.1f})")
    print(f"[serving_bench] slo_autoscale: auto attain "
          f"{auto['att']:.3f} ({auto['chips']:.0f} chip-s, "
          f"{len(ups)} up/{len(downs)} down, recover "
          f"{recover_s:.1f} s) vs static[{n_static}] "
          f"{static['att']:.3f} ({static['chips']:.0f} chip-s)",
          flush=True)
    return out


def _disagg_bench(params, base, infer_cfg):
    """Disaggregated prefill/decode A/B at EQUAL replica count
    (docs/serving.md "Disaggregated serving"): two identical
    2-replica fleets serve the same schedule — an interactive tenant
    decoding steadily while a batch tenant drip-feeds long prompts —
    one fleet colocated (role-less control), one role-specialized
    (1 prefill + 1 decode; interactive requests hand off after
    prefill). Reported:

      * `disagg_{colo,spec}_itl_ms_p99` — interactive inter-token
        p99: the specialized decode replica never runs an admission
        chunk, so the flood's prefill bursts stop landing in the
        interactive requests' token gaps;
      * `disagg_{colo,spec}_ttft_ms_p99` — interactive TTFT p99 (the
        guard: role-specialization must not regress first-token
        latency);
      * `disagg_handoffs` / `disagg_handoff_success_rate` — admitted
        continuations over attempted handoffs;
      * `disagg_itl_p99_ratio` — spec/colo (headline; < 1 is a win).

    Beyond the numbers the measured run ASSERTS the acceptance bar:
    strict interactive ITL p99 improvement, TTFT p99 within noise of
    the control, handoff success rate >= 0.95, and every handed-off
    request reading as exactly ONE gap-free span tree spanning both
    replicas (prefill half + `migrate_gap` seam + decode half).
    Both arms run twice (small untimed compile warm-up, then
    measured), like the other serving A/Bs."""
    import dataclasses

    from cloud_server_tpu.inference.paged_server import PagedInferenceServer
    from cloud_server_tpu.inference.request_trace import PHASES
    from cloud_server_tpu.inference.router import ReplicatedRouter

    cfg = dataclasses.replace(base, decode_attention_impl="pallas")
    qos_cfg = {"quantum": 64,
               "tenants": {
                   "inter": {"weight": 4.0, "priority": "interactive"},
                   "bulk": {"weight": 1.0, "priority": "batch"}}}

    def scenario(roles, inter_new, n_flood, check):
        def mk():
            return PagedInferenceServer(
                params, cfg, infer_cfg, max_slots=8, max_context=1024,
                page_size=128, prefill_chunk=256, decode_chunk=8,
                prompt_buckets=[64, 256], qos=qos_cfg, tracing=1.0)

        router = ReplicatedRouter([mk(), mk()], roles=roles)
        mk_prompt = make_prompt_fn(0)

        def handoffs_attempted():
            return router.metrics_snapshot()[
                "cloud_server_router_handoffs_total"]["value"]

        inter = [router.submit(mk_prompt(64), max_new_tokens=inter_new,
                               tenant="inter") for _ in range(6)]
        # settle: admission and (spec arm) every handoff complete
        # BEFORE the flood starts, so the seam gaps sit in the warm
        # window and the measured contrast is pure admission
        # interference
        for _ in range(12):
            router.step()
        if roles is not None:
            t_settle = time.perf_counter() + 30
            while (time.perf_counter() < t_settle
                   and handoffs_attempted() < len(inter)
                   and not all(r.done for r in inter)):
                router.step()
        flood = []
        steps = 0
        deadline = time.perf_counter() + 300
        # len() guard: the flood must fully submit even when the
        # interactive side already finished (the short warm-up runs),
        # or the warm-up never compiles the mixed admission shapes
        while ((len(flood) < n_flood
                or not all(r.done for r in inter + flood))
               and time.perf_counter() < deadline):
            # drip-feed: admission chunks keep landing for as long as
            # the interactive requests decode (the colocated fleet's
            # pain; one-shot floods finish admitting in a few steps)
            if steps % 2 == 0 and len(flood) < n_flood:
                flood += [router.submit(mk_prompt(256),
                                        max_new_tokens=24,
                                        tenant="bulk")
                          for _ in range(2)]
            router.step()
            steps += 1

        itl = [b - a for r in inter
               for a, b in zip(r.emit_times, r.emit_times[1:])]
        ttft = [r.emit_times[0] - r.submit_time for r in inter
                if r.emit_times]
        reqs = inter + flood
        res = {"itl_ms_p99": pct(itl, 0.99) * 1e3,
               "ttft_ms_p99": pct(ttft, 0.99) * 1e3,
               "completed_frac": sum(r.finish_reason == "length"
                                     for r in reqs) / len(reqs)}
        if roles is not None:
            snap = router.metrics_snapshot()
            att = snap["cloud_server_router_handoffs_total"]["value"]
            succ = snap["cloud_server_router_handoff_success_total"][
                "value"]
            res["handoffs"] = att
            res["handoff_success_rate"] = succ / max(att, 1)
        if check and roles is not None:
            # acceptance: EVERY handed-off request reads as exactly
            # ONE gap-free span tree spanning prefill -> decode
            trees = router.trace_trees()
            merged = [t for t in trees
                      if t["root"]["tags"].get("handoff_segments")]
            assert merged, "no handoff produced a merged span tree"
            by_id = {}
            for t in trees:
                by_id.setdefault(t["request_id"], []).append(t)
            for t in merged:
                assert len(by_id[t["request_id"]]) == 1, \
                    f"duplicate trees for {t['request_id']}"
                root = t["root"]
                tags = root["tags"]
                assert tags.get("decode_replica") is not None \
                    and tags["decode_replica"] != tags.get("replica"), \
                    tags
                assert root["end"] is not None, "unfinished merge"
                phases = [c for c in root["children"]
                          if c["name"] in PHASES]
                assert "migrate_gap" in [p["name"] for p in phases]
                assert phases[0]["start"] == root["start"]
                for a, b in zip(phases, phases[1:]):
                    assert a["end"] == b["start"], \
                        f"gap between {a['name']} and {b['name']}"
                assert phases[-1]["end"] == root["end"]
            # consumed continuations never leak as standalone trees
            assert not [t for t in trees
                        if t["root"]["tags"].get("handoff_of")], \
                "unmerged handoff continuation leaked"
        for r in inter + flood:
            r.cancel()
        router.run_until_idle()
        router.stop()
        return res

    out = {}
    for tag, roles in (("colo", None), ("spec", ["prefill", "decode"])):
        # warm-up runs the FULL workload shape (same flood count and
        # drip, short decode budgets): every mixed-step / continuation
        # admission variant compiles here, so no compile stall can
        # masquerade as an ITL gap in the measured run
        scenario(roles, 48, 12, check=False)
        res = scenario(roles, 256, 12, check=True)
        out[f"disagg_{tag}_itl_ms_p99"] = res["itl_ms_p99"]
        out[f"disagg_{tag}_ttft_ms_p99"] = res["ttft_ms_p99"]
        out[f"disagg_{tag}_completed_frac"] = res["completed_frac"]
        if roles is not None:
            out["disagg_handoffs"] = res["handoffs"]
            out["disagg_handoff_success_rate"] = \
                res["handoff_success_rate"]
        print(f"[serving_bench] disagg_{tag}: itl p99 "
              f"{res['itl_ms_p99']:.1f} ms, ttft p99 "
              f"{res['ttft_ms_p99']:.1f} ms, completed "
              f"{res['completed_frac']:.2f}"
              + (f", {res['handoffs']:.0f} handoffs (success "
                 f"{res['handoff_success_rate']:.2f})"
                 if roles is not None else ""), flush=True)
    out["disagg_itl_p99_ratio"] = (
        out["disagg_spec_itl_ms_p99"]
        / max(out["disagg_colo_itl_ms_p99"], 1e-9))
    # the acceptance bar, asserted where the numbers were measured
    assert out["disagg_handoffs"] >= 1, "no handoff ever attempted"
    assert out["disagg_handoff_success_rate"] >= 0.95, out
    assert (out["disagg_spec_itl_ms_p99"]
            < out["disagg_colo_itl_ms_p99"]), (
        "role-specialization did not improve interactive ITL p99: "
        f"{out}")
    # TTFT: no regression, within CPU-sandbox timer noise
    assert (out["disagg_spec_ttft_ms_p99"]
            <= out["disagg_colo_ttft_ms_p99"] * 1.10 + 25.0), (
        f"role-specialization regressed interactive TTFT p99: {out}")
    print(f"[serving_bench] disagg_itl_p99_ratio "
          f"{out['disagg_itl_p99_ratio']:.2f}", flush=True)
    return out


def _prefix_cache_churn_bench(params, base, infer_cfg):
    """Prefix-cache behavior under multi-tenant churn — the
    measurement half of ROADMAP item 3 (the policy half, prefix-aware
    routing + per-tenant quotas, will A/B against these keys as
    `prefix_cache_speedup`).

    Scenario: two tenants share one SYSTEM PROMPT (a 256-token header,
    exactly the fleet shape the radix cache exists for) and submit
    short unique continuations, while a third "flood" tenant streams
    pairwise-disjoint long prompts through a pool sized so the flood's
    churn must evict cached chains. Reports the page hit rate, the
    eviction rate per 1k emitted tokens, and the per-tenant
    saved-token split — plus asserts the attribution layer end-to-end:
    the shared header must be the hottest sketch chain, both header
    tenants must realize savings, and the flood tenant must show up
    as the eviction FORCER in the forensics matrix."""
    import dataclasses

    import numpy as np

    from cloud_server_tpu.inference.paged_server import PagedInferenceServer

    cfg = dataclasses.replace(base, decode_attention_impl="pallas")
    qos_cfg = {"tenants": {"team-a": {}, "team-b": {},
                           "flood": {"priority": "batch"}}}

    def scenario():
        # 44 pages x 128 tokens: the 12 disjoint 384-token flood
        # chains alone (~36 keyed pages) roll the cache over, so the
        # flood FORCES evictions while the LRU protects the re-hit
        # shared header — exactly the churn-vs-locality regime item
        # 3's quota policy will tune
        srv = PagedInferenceServer(
            params, cfg, infer_cfg, max_slots=16, max_context=1024,
            page_size=128, prefill_chunk=256, decode_chunk=8,
            prompt_buckets=[64, 256, 512], num_pages=44, qos=qos_cfg)
        rng = np.random.RandomState(7)
        header = [int(x) for x in rng.randint(1, 30000, size=256)]

        def flood_prompt():
            return [int(x) for x in rng.randint(1, 30000, size=384)]

        t0 = time.perf_counter()
        reqs = []
        for wave in range(4):
            for tenant in ("team-a", "team-b"):
                reqs += [srv.submit(header + [100 + wave, i],
                                    max_new_tokens=32, tenant=tenant)
                         for i in range(2)]
            reqs += [srv.submit(flood_prompt(), max_new_tokens=32,
                                tenant="flood") for _ in range(3)]
            for _ in range(6):
                srv.step()
        srv.run_until_idle()
        dt = time.perf_counter() - t0
        total = sum(len(r.tokens) for r in reqs)
        cs = srv.cache_stats()
        evictions = srv.allocator.evictions
        srv.stop()
        return cs, total, dt, evictions

    scenario()  # warm-up: compile every prefill/decode shape
    cs, total, dt, evictions = scenario()
    led = cs["tenants"]
    # end-to-end attribution asserts (guarded like the churn asserts)
    assert cs["prefix"]["hit_pages"] > 0, "shared header never hit"
    assert led["team-a"]["saved_tokens"] > 0, "team-a realized nothing"
    assert led["team-b"]["saved_tokens"] > 0, "team-b realized nothing"
    assert cs["top_prefixes"], "hot-prefix sketch is empty"
    # the 256-token header is 2 pages deep at page_size=128 — it must
    # be the hottest chain after 16 shared-header admissions
    assert cs["top_prefixes"][0]["depth"] >= 2, cs["top_prefixes"][0]
    if evictions:
        forcers = {f for row in cs["eviction_matrix"].values()
                   for f in row}
        assert "flood" in forcers, (
            f"evictions ran but the flood tenant forced none: "
            f"{cs['eviction_matrix']}")
    out = {
        "cache_hit_rate": cs["prefix"]["hit_rate"],
        "cache_evictions_per_1k_tok": 1e3 * evictions / max(total, 1),
        "cache_saved_tokens_team_a": led["team-a"]["saved_tokens"],
        "cache_saved_tokens_team_b": led["team-b"]["saved_tokens"],
        "cache_saved_tokens_flood": led["flood"]["saved_tokens"],
        "cache_evicted_pages_team_a": led["team-a"]["evicted_pages"],
        "cache_top_prefix_hits": cs["top_prefixes"][0]["hits"],
        "prefix_churn_tok_s": total / dt,
    }
    print(f"[serving_bench] prefix_cache_churn: hit_rate "
          f"{out['cache_hit_rate']:.3f}, "
          f"{out['cache_evictions_per_1k_tok']:.1f} evictions/1k tok, "
          f"saved a/b/flood: {out['cache_saved_tokens_team_a']}/"
          f"{out['cache_saved_tokens_team_b']}/"
          f"{out['cache_saved_tokens_flood']}", flush=True)
    return out


def _spec_churn_bench(params, base, infer_cfg):
    """Speculation composed with stall-free batching: an A/B under
    admission churn on a repetition-heavy prompt mix (the n-gram sweet
    spot — code/tables-like local repetition):

      * `churn_spec_*`            — ADAPTIVE n-gram speculation (the
                                    default controller);
      * `churn_spec_mixed_plain_*` — no speculation (what the
                                    speculative arm must beat for the
                                    window to pay under churn).

    A third arm, `churn_spec_draft_model_*`, drives DRAFT-MODEL
    speculation through the same churn scenario, one fused dispatch
    per iteration. Its accept rate reflects the random-init
    draft here (the controller walks poor acceptors off); trained
    draft-model acceptance is measured by the trained_spec section.

    Every arm reports tok/s, decode-ITL p99 (ms, the equal-latency
    check), and — speculative arms — committed tokens per decode round.
    A final pair measures the ADAPTIVE FLOOR on the random-prompt
    (low-acceptance) mix: `spec_adaptive_floor_ratio` = adaptive-spec
    tok/s / plain tok/s, which must hover ~1.0 — the controller walks
    every slot to plain decode instead of paying dead verify windows.
    Each scenario runs twice (untimed compile warm-up, then timed)."""
    import dataclasses

    import numpy as np

    from cloud_server_tpu.models import transformer

    from cloud_server_tpu.inference.paged_server import PagedInferenceServer

    cfg = dataclasses.replace(base, decode_attention_impl="pallas")
    # greedy so acceptance reflects the model, not sampling noise
    greedy = dataclasses.replace(infer_cfg, temperature=0.0)
    # tiny random-init draft sharing the target's vocab: exercises the
    # fused draft prefill/decode discipline under churn (acceptance is
    # draft-quality dependent; see docstring)
    draft_cfg = dataclasses.replace(
        base, embed_dim=256, num_layers=2, num_heads=4, num_kv_heads=4,
        mlp_dim=1024)
    draft_params = transformer.init_params(draft_cfg, jax.random.key(11))

    def scenario(spec, spec_control, rep, draft=False):
        # every arm (and each arm's warm-up vs timed run) draws the
        # IDENTICAL prompt sequence: the A/B ratios must compare
        # the arms, not prompt-mix noise
        rng = np.random.RandomState(3)

        def mk(n):
            if rep:
                pat = [int(x) for x in rng.randint(1, 30000, size=8)]
                return (pat * (n // 8 + 1))[:n]
            return [int(x) for x in rng.randint(1, 30000, size=n)]
        srv = PagedInferenceServer(
            params, cfg, greedy, max_slots=16, max_context=1024,
            page_size=128, prefill_chunk=256, decode_chunk=8,
            prompt_buckets=[64, 256, 512],
            spec_drafts=spec, spec_control=spec_control,
            draft_params=draft_params if draft else None,
            draft_cfg=draft_cfg if draft else None)
        first = [srv.submit(mk(64), max_new_tokens=256)
                 for _ in range(8)]
        for _ in range(2):
            srv.step()
        t0 = time.perf_counter()
        r0, c0 = srv.decode_rounds, srv.decode_tokens_committed
        waves = []
        # three waves of long admissions while the first batch decodes
        for _ in range(3):
            waves += [srv.submit(mk(400), max_new_tokens=128)
                      for _ in range(4)]
            for _ in range(6):
                srv.step()
        srv.run_until_idle()
        dt = time.perf_counter() - t0
        total = sum(len(r.tokens) for r in first + waves)
        accept = ((srv.decode_tokens_committed - c0)
                  / max(srv.decode_rounds - r0, 1))
        itls = []
        for r in first:
            itls += [b - a for a, b in zip(r.emit_times,
                                           r.emit_times[1:])]
        itls.sort()
        p99 = (itls[min(len(itls) - 1, int(0.99 * len(itls)))]
               if itls else 0.0)
        srv.stop()
        return total / dt, accept, p99 * 1e3

    out = {}
    arms = {
        # (spec_drafts, spec_control, repetitive, draft)
        "churn_spec": (3, None, True, False),  # adaptive dflt
        "churn_spec_mixed_plain": (0, False, True, False),
        "churn_spec_draft_model": (3, None, True, True),
        "spec_adaptive_random": (3, None, False, False),
        "spec_plain_random": (0, False, False, False),
    }
    for tag, (spec, ctl, rep, draft) in arms.items():
        scenario(spec, ctl, rep, draft)  # warm-up compiles
        tok_s, accept, itl_p99 = scenario(spec, ctl, rep, draft)
        out[f"{tag}_tok_s"] = tok_s
        out[f"{tag}_itl_ms_p99"] = itl_p99
        if spec:
            out[f"{tag}_accept"] = accept
        print(f"[serving_bench] {tag}: {tok_s:.1f} tok/s, itl_p99 "
              f"{itl_p99:.1f} ms"
              + (f", accept {accept:.2f} tok/round" if spec else ""),
              flush=True)
    out["churn_spec_speedup_vs_plain"] = (
        out["churn_spec_tok_s"]
        / max(out["churn_spec_mixed_plain_tok_s"], 1e-9))
    out["spec_adaptive_floor_ratio"] = (
        out["spec_adaptive_random_tok_s"]
        / max(out["spec_plain_random_tok_s"], 1e-9))
    print(f"[serving_bench] churn_spec speedup: "
          f"{out['churn_spec_speedup_vs_plain']:.2f}x vs plain; "
          f"adaptive floor "
          f"{out['spec_adaptive_floor_ratio']:.2f}", flush=True)
    return out


def _check_span_trees(srv, reqs):
    """Trace-side integrity check (the span analogue of the
    churn_srv_* histogram agreement): a fully-sampled run produced
    exactly ONE span tree per request; each tree's phase spans are
    monotonic and GAP-FREE (every phase starts exactly where the
    previous ended, covering submit -> finish); and the span
    boundaries agree with the request's own externally recorded
    timing (root start == submit_time, first prefill ends at the
    first emit)."""
    from cloud_server_tpu.inference.request_trace import PHASES
    trees = srv.trace_trees()
    assert len(trees) == len(reqs), (
        f"{len(trees)} span trees for {len(reqs)} requests")
    by_id = {t["request_id"]: t for t in trees}
    assert len(by_id) == len(reqs), "duplicate trees for one request"
    for r in reqs:
        root = by_id[r.request_id]["root"]
        assert root["start"] == r.submit_time
        assert root["end"] is not None, "unfinished tree after idle"
        phases = [c for c in root["children"] if c["name"] in PHASES]
        names = [p["name"] for p in phases]
        for want in ("queue", "prefill", "decode", "emit"):
            assert want in names, f"missing {want} in {names}"
        assert phases[0]["start"] == root["start"]
        for a, b in zip(phases, phases[1:]):
            assert a["end"] == b["start"], \
                f"gap between {a['name']} and {b['name']}"
        assert phases[-1]["end"] == root["end"]
        if r.emit_times:
            first_prefill = next(p for p in phases
                                 if p["name"] == "prefill")
            assert first_prefill["end"] == r.emit_times[0]


# Churn-section SLO config (no QoS registry -> every request rides the
# "default" class): generous targets so attainment reads the
# scheduler, not the fixed cost of a dispatch.
_CHURN_SLO_CFG = {
    "windows_s": [60, 300],
    "classes": {"default": {"objective": 0.99, "ttft_s": 2.0,
                            "itl_s": 1.0, "queue_wait_s": 2.0,
                            "e2e_s": 300.0}}}


def _churn_scenario(params, base, infer_cfg):
    """Continuous batching under churn: requests arrive in waves while
    others decode, and every step fuses the chunked prefills with the
    decode rows in one token-budget dispatch.

    The scenario runs TWICE: once untimed to compile every dispatch
    shape it triggers, then timed with all shapes warm. Reports
    completed-token throughput, interleaved-decode count, the decode
    throughput SUSTAINED WHILE ADMISSIONS RUN, and the request-level
    latencies chunked prefill exists to bound: TTFT for the long
    prompts that land mid-decode, and inter-token-latency percentiles
    for the requests decoding while those admissions run."""
    import dataclasses

    from cloud_server_tpu.inference.paged_server import PagedInferenceServer

    cfg = dataclasses.replace(base, decode_attention_impl="pallas")

    def scenario():
        # max_slots leaves headroom beyond the initial decode batch so a
        # wave admission lands MID-DECODE (the thing TTFT measures here)
        # instead of queueing for a free slot. Tracing at FULL sampling
        # + SLO tracking ride along: the bench is also the standing
        # proof that both layers cost nothing measurable (the
        # dispatch-count regression test pins the zero-dispatch
        # invariant; the A/B here would show any host-side drag).
        srv = PagedInferenceServer(
            params, cfg, infer_cfg, max_slots=16, max_context=1024,
            page_size=128, prefill_chunk=256, decode_chunk=8,
            prompt_buckets=[64, 256, 512],
            tracing=1.0, slo=_CHURN_SLO_CFG)
        mk_prompt = make_prompt_fn(0)

        first = [srv.submit(mk_prompt(64), max_new_tokens=256)
                 for _ in range(8)]
        for _ in range(2):
            srv.step()
        t0 = time.perf_counter()
        interleaved = 0
        dec_tok_adm = 0      # first-batch tokens landed while admitting
        t_adm = 0.0          # wall time of admitting steps
        waves = []
        # three waves of long-prompt arrivals while the first batch decodes
        for _ in range(3):
            waves += [srv.submit(mk_prompt(400), max_new_tokens=128)
                      for _ in range(4)]
            for _ in range(6):
                admitting = bool(srv._jobs) or srv.num_pending > 0
                n0 = sum(len(r.tokens) for r in first)
                ts = time.perf_counter()
                srv.step()
                te = time.perf_counter()
                if admitting:
                    t_adm += te - ts
                    dec_tok_adm += sum(len(r.tokens) for r in first) - n0
                    if srv.active.any():
                        interleaved += 1
        srv.run_until_idle()
        dt = time.perf_counter() - t0
        snap = srv.metrics_snapshot()  # server-side telemetry, pre-stop
        flight = srv.flight_window()
        # one span tree per request, gap-free phases, agrees with the
        # request objects' own timing (full-sampling integrity check)
        _check_span_trees(srv, first + waves)
        slo_rep = srv.slo_report()
        srv.stop()
        return first, waves, dt, interleaved, dec_tok_adm, t_adm, \
            snap, flight, slo_rep

    scenario()  # warm-up: every prefill/decode shape compiles here
    (first, waves, dt, interleaved, dec_tok_adm, t_adm,
     snap, flight, slo_rep) = scenario()

    total = sum(len(r.tokens) for r in first + waves)

    ttfts = [r.emit_times[0] - r.submit_time
             for r in waves if r.emit_times]
    itls = []
    for r in first:
        itls += [b - a for a, b in zip(r.emit_times, r.emit_times[1:])]

    # Server-side lifecycle telemetry vs the external measurement: the
    # in-server TTFT histogram observed emit_times[0] - submit_time at
    # emit time, so its mean over ALL requests must agree with the same
    # quantity recomputed here from the request objects — a disagreement
    # means the telemetry path dropped or double-counted observations.
    from cloud_server_tpu.utils.serving_metrics import (
        histogram_percentile)
    h_ttft = snap["cloud_server_ttft_seconds"]
    h_itl = snap["cloud_server_itl_seconds"]
    ext_ttft = [r.emit_times[0] - r.submit_time
                for r in first + waves if r.emit_times]
    assert h_ttft["count"] == len(ext_ttft), (
        f"server TTFT count {h_ttft['count']} != external "
        f"{len(ext_ttft)}")
    ext_mean = sum(ext_ttft) / len(ext_ttft)
    srv_mean = h_ttft["sum"] / h_ttft["count"]
    assert abs(srv_mean - ext_mean) <= 0.05 * ext_mean + 5e-3, (
        f"server TTFT mean {srv_mean * 1e3:.1f} ms disagrees with "
        f"external {ext_mean * 1e3:.1f} ms")
    util = [rec["budget_utilization"] for rec in flight
            if "budget_utilization" in rec]
    # Iteration-phase profile of the same run: the host-gap fraction
    # is the serialized host cost per iteration, over the records of
    # steps that committed a program: the residual commit/launch/
    # epilogue tail, with the hidden sweep/admission/build/deliver in
    # overlap_ms. The per-record identity host_ms + device_wait_ms +
    # overlap_ms == duration_ms is asserted (the phase clock
    # partitions the iteration by construction).
    ph_recs = [rec for rec in flight if "host_ms" in rec]
    assert ph_recs, "profiling-enabled run produced no phase records"
    for rec in ph_recs:
        assert abs(rec["host_ms"] + rec["device_wait_ms"]
                   + rec["overlap_ms"]
                   - rec["duration_ms"]) <= 1e-6 * rec["duration_ms"] \
            + 1e-6, f"phase split does not partition the iteration: {rec}"
    host_gap = (sum(r["host_ms"] for r in ph_recs)
                / max(sum(r["duration_ms"] for r in ph_recs), 1e-9))
    phase_keys = {}
    for ph in ("admission", "build", "device", "commit", "launch",
               "epilogue"):
        vals = [r["phases_ms"].get(ph, 0.0) for r in ph_recs]
        phase_keys[f"churn_phase_ms_{ph}_p50"] = pct(vals, 0.50)
    leads = [r["overlap_launch_lead_ms"] for r in ph_recs
             if "overlap_launch_lead_ms" in r]
    if leads:
        phase_keys["churn_overlap_launch_lead_ms_p50"] = pct(leads, 0.50)
        phase_keys["churn_overlap_frac_iterations"] = (
            len(leads) / len(ph_recs))
    # SLO view of the same run (lifetime counts — deterministic, no
    # window-edge sensitivity): default-class attainment per metric
    slo_keys = {}
    for metric in ("ttft", "itl"):
        life = (slo_rep["classes"]["default"]["metrics"][metric]
                ["lifetime"])
        att = life["attainment"]
        slo_keys[f"churn_slo_attainment_{metric}"] = (
            1.0 if att is None else att)
    return {**slo_keys,
            "churn_tok_s": total / dt,
            "churn_decode_steps_during_admission": interleaved,
            "churn_decode_tok_s_during_admission":
                dec_tok_adm / max(t_adm, 1e-9),
            "churn_ttft_ms_p50": pct(ttfts, 0.50) * 1e3,
            "churn_ttft_ms_p95": pct(ttfts, 0.95) * 1e3,
            "churn_itl_ms_p50": pct(itls, 0.50) * 1e3,
            "churn_itl_ms_p99": pct(itls, 0.99) * 1e3,
            # server-side histogram view (validated against external)
            "churn_srv_ttft_ms_mean": srv_mean * 1e3,
            "churn_srv_ttft_ms_p95":
                histogram_percentile(h_ttft, 0.95) * 1e3,
            "churn_srv_itl_ms_p50":
                histogram_percentile(h_itl, 0.50) * 1e3,
            "churn_srv_itl_ms_p99":
                histogram_percentile(h_itl, 0.99) * 1e3,
            "churn_budget_utilization_mean":
                sum(util) / len(util) if util else 0.0,
            # host-gap attribution (iteration_profile.py): the share
            # of each iteration in the host's serialized tail, per
            # phase
            "churn_host_gap_frac": host_gap,
            **phase_keys}


def _qos_isolation_bench(params, base, infer_cfg):
    """Multi-tenant QoS isolation under overload, A/B over the
    aggressor: a steady "inter" tenant (interactive, weight 3) decodes
    while a "scraper" tenant (batch class, weight 1) floods the queue
    past slot capacity on a page pool sized to force preemption.
    Three runs on the QoS-enabled server geometry, each also tracked
    against per-class SLO targets (`slo_attainment_interactive` /
    `slo_attainment_batch` report the flood run's lifetime TTFT
    attainment per class — the isolation story in SLO terms):

      * aggressor OFF  -> the victim's uncontended tok/s + ITL p99;
      * aggressor ON, QoS ON  -> fair-share admission + priority
        preemption protect the victim (scraper slots are the victims);
      * aggressor ON, QoS OFF -> the FIFO/youngest-preemption control.

    `qos_isolation_ratio` = victim tok/s (aggressor on, QoS on) /
    victim tok/s (aggressor off) — 1.0 is perfect isolation;
    `qos_off_isolation_ratio` is the same ratio for the control, so
    the headline A/B is the gap between the two. Each scenario runs
    twice (untimed compile warm-up, then timed) like the churn bench."""
    import dataclasses

    from cloud_server_tpu.inference.paged_server import PagedInferenceServer

    cfg = dataclasses.replace(base, decode_attention_impl="pallas")
    # "batch" (not best_effort) for the aggressor: victim selection is
    # unchanged — preemption still targets the lowest class first —
    # and the run now exercises BOTH SLO classes the per-class
    # attainment keys report on (slo_attainment_{interactive,batch})
    qos_cfg = {"quantum": 64,
               "tenants": {
                   "inter": {"weight": 3.0, "priority": "interactive"},
                   "scraper": {"weight": 1.0, "priority": "batch"}}}
    slo_cfg = {"windows_s": [60, 300],
               "classes": {
                   "interactive": {"objective": 0.99, "ttft_s": 2.0,
                                   "itl_s": 1.0, "e2e_s": 300.0},
                   "batch": {"objective": 0.9, "ttft_s": 10.0,
                             "e2e_s": 600.0}}}

    def scenario(aggressor: bool, qos):
        # 16 slots x 8 pages/slot worst case = 128; 72 pages forces
        # on-demand preemption once the flood's chains deepen — the
        # regime victim selection (priority vs youngest) decides
        srv = PagedInferenceServer(
            params, cfg, infer_cfg, max_slots=16, max_context=1024,
            page_size=128, prefill_chunk=256, decode_chunk=8,
            prompt_buckets=[64, 256], num_pages=72, qos=qos,
            slo=slo_cfg)
        mk_prompt = make_prompt_fn(0)

        victims = [srv.submit(mk_prompt(64), max_new_tokens=512,
                              tenant="inter") for _ in range(6)]
        for _ in range(2):
            srv.step()
        aggr = ([srv.submit(mk_prompt(64), max_new_tokens=512,
                            tenant="scraper") for _ in range(24)]
                if aggressor else [])
        v0 = sum(len(r.tokens) for r in victims)
        a0 = sum(len(r.tokens) for r in aggr)
        t0 = time.perf_counter()
        for _ in range(16):
            srv.step()
        dt = time.perf_counter() - t0
        v_tok_s = (sum(len(r.tokens) for r in victims) - v0) / dt
        a_tok_s = (sum(len(r.tokens) for r in aggr) - a0) / dt
        itls = []
        for r in victims:
            gaps = [b - a for a, b in zip(r.emit_times, r.emit_times[1:])
                    if b >= t0]
            itls += gaps
        itls.sort()
        p99 = itls[min(len(itls) - 1, int(0.99 * len(itls)))] if itls \
            else 0.0
        # per-class TTFT attainment (lifetime counts: deterministic)
        # BEFORE the cancel sweep pollutes e2e with cancellations
        rep = srv.slo_report()

        def attain(cls):
            m = rep["classes"].get(cls, {}).get("metrics", {})
            att = m.get("ttft", {}).get("lifetime", {}).get("attainment")
            return 1.0 if att is None else att

        for r in victims + aggr:
            r.cancel()
        srv.run_until_idle()
        srv.stop()
        return {"victim_tok_s": v_tok_s, "aggressor_tok_s": a_tok_s,
                "victim_itl_ms_p99": p99 * 1e3,
                "slo_attainment_interactive": attain("interactive"),
                "slo_attainment_batch": attain("batch")}

    out = {}
    # qos=False force-disables (None would fall back to any
    # InferConfig.qos_config, silently turning the control arm on)
    cases = [("alone", False, qos_cfg), ("flood", True, qos_cfg),
             ("flood_noqos", True, False)]
    for tag, aggressor, qos in cases:
        scenario(aggressor, qos)  # warm-up: compile every shape
        res = scenario(aggressor, qos)
        out[f"qos_{tag}_victim_tok_s"] = res["victim_tok_s"]
        out[f"qos_{tag}_itl_ms_p99"] = res["victim_itl_ms_p99"]
        if aggressor:
            out[f"qos_{tag}_aggressor_tok_s"] = res["aggressor_tok_s"]
        if tag == "flood":  # the QoS-on overload run: the per-class
            # SLO view of isolation (lifetime TTFT attainment)
            out["slo_attainment_interactive"] = \
                res["slo_attainment_interactive"]
            out["slo_attainment_batch"] = res["slo_attainment_batch"]
        print(f"[serving_bench] qos_{tag}: victim "
              f"{res['victim_tok_s']:.1f} tok/s, itl p99 "
              f"{res['victim_itl_ms_p99']:.1f} ms, aggressor "
              f"{res['aggressor_tok_s']:.1f} tok/s", flush=True)
    alone = max(out["qos_alone_victim_tok_s"], 1e-9)
    out["qos_isolation_ratio"] = out["qos_flood_victim_tok_s"] / alone
    out["qos_off_isolation_ratio"] = (
        out["qos_flood_noqos_victim_tok_s"] / alone)
    print(f"[serving_bench] qos_isolation_ratio "
          f"{out['qos_isolation_ratio']:.2f} (qos off: "
          f"{out['qos_off_isolation_ratio']:.2f})", flush=True)
    return out


def _trained_spec_bench():
    """Speculative decoding measured on a TRAINED model + natural text.

    r3's acceptance numbers came from an untrained model decoding
    greedily — which collapses to repetition on ANY prompt, so its
    'random-prompt' row measured the same degenerate regime. Here the
    framework's own pipeline (byte tokenizer -> memmap -> training
    loop) trains a small byte-level LM on this repo's source code
    (tests/ held out), plus a 4x-smaller draft model, then serves
    held-out code through the paged server three ways: plain, n-gram
    speculation, and in-server draft-model speculation. Acceptance
    rates are per committed-tokens-per-round (1.0 = no speculation
    win).

    Read the ACCEPT columns, not tok/s: this model is deliberately tiny
    (trainable inside the bench), so serving it is per-dispatch-overhead
    bound and the (G+1)-token verify window (plus G+1 draft forwards on
    the draft row) costs several thin-model forwards' overhead for <3x
    the tokens — speculation cannot pay that back HERE. The 330M
    `decode_tok_s_pallas_spec_*` rows are where the wall-clock win
    lives (weights-streaming-bound, window nearly free); this section's
    job is the acceptance evidence the r3 bench lacked: a TRAINED model
    on natural held-out text (r4 measured: n-gram 1.64, draft-model
    2.63 committed tokens/round; r5, run standalone outside the
    driver's time budget: n-gram 1.58, draft-model 2.78 — stable
    round-over-round)."""
    import dataclasses
    import glob as _glob

    import numpy as np

    from cloud_server_tpu.config import InferConfig, ModelConfig, TrainConfig
    from cloud_server_tpu.inference.paged_server import PagedInferenceServer
    from cloud_server_tpu.parallel.mesh import make_mesh
    from cloud_server_tpu.config import MeshConfig
    from cloud_server_tpu.training import init_train_state, make_train_step

    here = os.path.dirname(os.path.abspath(__file__))
    src = sorted(_glob.glob(os.path.join(here, "cloud_server_tpu", "**",
                                         "*.py"), recursive=True))
    corpus = b"".join(open(f, "rb").read() for f in src)
    held = sorted(_glob.glob(os.path.join(here, "tests", "*.py")))
    held_text = b"".join(open(f, "rb").read() for f in held)
    data = np.frombuffer(corpus, np.uint8).astype(np.int32)

    seq = 256

    def train_one(cfg, steps, seed):
        mesh = make_mesh(MeshConfig())
        tcfg = TrainConfig(batch_size=16, seq_len=seq, warmup_steps=20,
                           total_steps=steps, learning_rate=3e-3)
        state = init_train_state(cfg, tcfg, mesh, jax.random.key(seed))
        step, batch_sharding = make_train_step(cfg, tcfg, mesh)
        rng = np.random.RandomState(seed)
        loss = None
        for i in range(steps):
            starts = rng.randint(0, len(data) - seq - 1, size=16)
            toks = np.stack([data[s:s + seq] for s in starts])
            state, metrics = step(state, {"tokens": jnp.asarray(toks)})
            if i == steps - 1:
                loss = float(jax.device_get(metrics["loss"]))
        print(f"[trained_spec] trained {cfg.num_layers}L/"
              f"{cfg.embed_dim}d {steps} steps, final loss {loss:.3f}",
              flush=True)
        return jax.device_get(state.params)

    target_cfg = ModelConfig(
        vocab_size=259, embed_dim=256, num_layers=4, num_heads=4,
        num_kv_heads=4, head_dim=64, mlp_dim=1024, max_seq_len=1024,
        dtype="bfloat16", param_dtype="float32", remat="none")
    draft_cfg = dataclasses.replace(target_cfg, embed_dim=128,
                                    num_layers=1, mlp_dim=512)
    t_params = train_one(target_cfg, 400, 0)
    d_params = train_one(draft_cfg, 400, 1)

    # held-out natural prompts: code text the model never trained on
    hrng = np.random.RandomState(3)
    prompts = []
    for _ in range(8):
        s = hrng.randint(0, len(held_text) - 129)
        prompts.append([int(b) for b in held_text[s:s + 128]])
    greedy = InferConfig(max_decode_len=256, temperature=0.0,
                         eos_token_id=-1, pad_token_id=0)
    serve_cfg = dataclasses.replace(target_cfg,
                                    decode_attention_impl="pallas")

    out = {}

    def run(tag, spec, draft=False):
        srv = PagedInferenceServer(
            t_params, serve_cfg, greedy, max_slots=8, max_context=512,
            page_size=128, prefill_chunk=256, decode_chunk=16,
            spec_drafts=spec, prompt_buckets=[128],
            draft_params=d_params if draft else None,
            draft_cfg=draft_cfg if draft else None)

        def full_run():
            for p in prompts:
                srv.submit(p, max_new_tokens=256)
            before, r0, c0 = (srv.tokens_emitted, srv.decode_rounds,
                              srv.decode_tokens_committed)
            t0 = time.perf_counter()
            srv.run_until_idle()
            dt = time.perf_counter() - t0
            return (srv.tokens_emitted - before,
                    srv.decode_rounds - r0,
                    srv.decode_tokens_committed - c0, dt)

        # untimed pass compiles every dispatch the run triggers — the
        # round count shrinks (16 -> 8 -> ... -> 1) as budgets drain,
        # and each count is its own Mosaic compile; the timed
        # pass then measures serving, not compilation
        full_run()
        toks, rounds, committed, dt = full_run()
        out[tag] = toks / dt
        if spec:
            out[tag + "_accept"] = committed / max(rounds, 1)
            print(f"[trained_spec] {tag}: {out[tag]:.1f} tok/s, "
                  f"accept {out[tag + '_accept']:.2f}", flush=True)
        else:
            print(f"[trained_spec] {tag}: {out[tag]:.1f} tok/s",
                  flush=True)
        srv.stop()

    run("trained_tok_s_plain", 0)
    run("trained_tok_s_ngram_spec", 3)
    run("trained_tok_s_draft_spec", 3, draft=True)
    return out


def _robust_attn_us(make_body, q, bytes_read: float,
                    n_meas: int = 5) -> tuple[float, float, int]:
    """(median_us, relative spread, n_rejected) over `n_meas`
    differential estimates, REJECTING the physically impossible: an
    estimate implying more than ~1.1x the HBM roofline's bandwidth for
    `bytes_read` is a harness artifact, not a measurement (r3 published
    12.0 us for a 33.6 MB read — 2.8 TB/s on a 0.8 TB/s part — and it
    rode into the round's headline). Spread is (max-min)/median of the
    survivors; callers treat spread > 0.5 as 'do not quote'."""
    from jax import lax

    def scan_of(n):
        def fn(q0):
            def f(qq, _):
                return make_body(qq).astype(qq.dtype), None
            return lax.scan(f, q0, None, length=n)[0]
        return fn

    # 100/1600: at ~50-500 us/iter the 1500-iter delta dwarfs the
    # fixed cost's variance (negative estimates otherwise)
    ests = diff_time_scan_multi(scan_of, (q,), 100, 1600, reps=3,
                                n_meas=n_meas)
    floor_s = bytes_read / (device_peaks().hbm_bytes_per_s * 1.1)
    ok = [e for e in ests if e >= floor_s]
    rejected = len(ests) - len(ok)
    if not ok:  # all impossible: report the floor-clamped median, loudly
        med = sorted(ests)[len(ests) // 2]
        return max(med, floor_s) * 1e6, 999.0, rejected
    med = sorted(ok)[len(ok) // 2]
    spread = (max(ok) - min(ok)) / med if med > 0 else 999.0
    return med * 1e6, spread, rejected


def _longcontext_attention_bench():
    """Decode attention, paged kernel vs XLA dense, differential scan
    timing (fixed cost cancelled) with roofline-rejected repeats (see
    _robust_attn_us). Three cases:
      * S=1024 full-length (B=8) — XLA's best shape, near roofline;
        parity expected (r3/r4 history: see docs/serving.md).
      * S=8192 full-length (B=2) — long-context decode.
      * RAGGED S=1024 (B=8, true lens 128..1024) — the shape the paged
        kernel exists for: it reads only each row's true pages while
        dense attention streams the full padded (B, S) KV. This is the
        serving steady state (requests at mixed depths), and the row the
        kernel's length-bounded claim is judged by."""
    import numpy as np

    from cloud_server_tpu.ops.attention import causal_attention
    from cloud_server_tpu.ops.paged_attention import paged_attention

    out = {}
    KH = H = 16
    D, PS = 64, 128
    cases = [("attn1k", 1024, 8, None),
             ("attn8k", 8192, 2, None),
             ("attn_ragged", 1024, 8,
              [128, 256, 384, 512, 640, 768, 896, 1024])]
    for tag, S, b, true_lens in cases:
        try:
            _attn_case(out, tag, S, b, true_lens, KH, H, D, PS)
        except Exception as exc:  # noqa: BLE001 — keep the cases
            # already measured (r5 lost attn8k+ragged to one failed
            # compile that voided the whole section)
            print(f"[serving_bench] {tag} skipped after error: {exc!r}",
                  flush=True)
            out[f"{tag}_error"] = repr(exc)[:160]
    return out


def _attn_case(out, tag, S, b, true_lens, KH, H, D, PS):
    import numpy as np

    from cloud_server_tpu.ops.attention import causal_attention
    from cloud_server_tpu.ops.paged_attention import paged_attention

    mp = S // PS
    num_pages = b * mp
    ks = jax.random.split(jax.random.key(1), 4)
    k_pool = jax.random.normal(ks[0], (1, num_pages, KH, D, PS),
                               jnp.bfloat16)
    v_pool = jax.random.normal(ks[1], (1, num_pages, KH, D, PS),
                               jnp.bfloat16)
    tables = jnp.asarray(
        np.random.RandomState(0).permutation(num_pages).reshape(b, mp),
        jnp.int32)
    k_cat = jax.random.normal(ks[2], (b, S, KH, D), jnp.bfloat16)
    v_cat = jax.random.normal(ks[3], (b, S, KH, D), jnp.bfloat16)
    lens = jnp.asarray(true_lens if true_lens is not None
                       else [S] * b, jnp.int32)
    q = jax.random.normal(ks[2], (b, 1, H, D), jnp.bfloat16)

    # K+V bf16 bytes actually required: the kernel reads page-rounded
    # true lengths; dense XLA streams the full padded extent
    kern_tokens = sum(-(-int(l) // PS) * PS for l in lens)
    kern_bytes = 2 * kern_tokens * KH * D * 2
    xla_bytes = 2 * b * S * KH * D * 2

    us_k, sp_k, rej_k = _robust_attn_us(
        lambda qq: paged_attention(qq, k_pool, v_pool, lens, tables,
                                   0, pages_per_block=8,
                                   interpret=False),
        q, kern_bytes)
    us_x, sp_x, rej_x = _robust_attn_us(
        lambda qq: causal_attention(qq, k_cat, v_cat,
                                    q_positions=(lens - 1)[:, None],
                                    kv_length=lens),
        q, xla_bytes)
    out[f"{tag}_us_pallas"] = us_k
    out[f"{tag}_us_xla"] = us_x
    out[f"{tag}_spread"] = round(max(sp_k, sp_x), 3)
    if rej_k or rej_x:
        out[f"{tag}_rejected_samples"] = rej_k + rej_x
    if true_lens is not None:
        out[f"{tag}_kernel_speedup"] = round(us_x / us_k, 3)
    print(f"[serving_bench] {tag} pallas/xla us: {us_k:.1f}/{us_x:.1f}"
          f" spread {max(sp_k, sp_x):.2f}"
          f" rejected {rej_k + rej_x}", flush=True)
    return out


def main() -> None:
    """Headline-first protocol: the driver tail-parses the LAST complete
    JSON line, and its time budget is finite — r4 learned this the hard
    way (rc=124 with the only print at the very end: no parsed number
    for the round). So the headline line is printed IMMEDIATELY after
    train_bench, then RE-printed with richer extras after every section
    that completes — a timeout or a failure mid-section still leaves
    a valid, maximally-enriched earlier line. The expensive trained-spec
    section (trains two models in-bench; its r4 acceptance numbers —
    n-gram 1.64, draft 2.63 — are kept in its docstring as provenance)
    runs LAST and only inside the time budget."""
    enable_compile_cache()
    t_start = time.perf_counter()
    base_tag, base = _baseline_tokens_per_sec()

    train = train_bench()
    extra = {
        "step_time_ms": round(train["step_time_ms"], 2),
        "approx_mfu": round(train["approx_mfu"], 4),
        "device": str(jax.devices()[0]),
        "baseline_round": base_tag,
    }

    def emit() -> None:
        # ONE self-contained JSON line per call, atomically flushed
        print(json.dumps({
            "metric": "train_tokens_per_sec_330M_bf16",
            "value": round(train["tokens_per_sec"], 1),
            "unit": "tokens/s",
            "vs_baseline": (round(train["tokens_per_sec"] / base, 4)
                            if base > 0 else 1.0),
            "extra": extra,
        }), flush=True)

    emit()  # the driver has a parsed headline (incl. MFU) from here on

    def section(name: str, skip_env: str | None, fn, ndigits: int) -> None:
        if skip_env and os.environ.get(skip_env) == "1":
            return
        try:
            rows = fn()
        except Exception as exc:  # noqa: BLE001 — keep earlier sections
            print(f"[bench] section {name} skipped after error: {exc!r}",
                  flush=True)
            extra[f"{name}_error"] = repr(exc)[:200]
        else:
            extra.update({k: round(v, ndigits) if isinstance(v, float)
                          else v for k, v in rows.items()})
        emit()

    section("longseq", "BENCH_SKIP_LONGSEQ", longseq_attention_bench, 2)
    section("serving", "BENCH_SKIP_SERVING", serving_bench, 1)
    section("longcontext_attn", "BENCH_SKIP_SERVING",
            _longcontext_attention_bench, 2)

    budget_s = float(os.environ.get("BENCH_TIME_BUDGET_S", "1500"))
    elapsed = time.perf_counter() - t_start
    if os.environ.get("BENCH_SKIP_SERVING") != "1" and elapsed < budget_s:
        section("trained_spec", None, _trained_spec_bench, 1)
    else:
        extra["trained_spec_skipped_at_s"] = round(elapsed, 1)
        emit()


if __name__ == "__main__":
    # timings come from the chip: name the device, refuse anything else
    require_tpu("bench")
    main()

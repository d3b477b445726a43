"""Text generation CLI: `python -m cloud_server_tpu.generate`.

Loads model params from a training checkpoint (or random-inits for smoke
runs), tokenizes prompts, and serves them through the paged
continuous-batching server (`PagedInferenceServer` — block-table KV,
radix prefix reuse, chunked prefill, optional in-server speculative
decoding via `--spec-drafts`). The tokenizer is byte-level by default
or a local HuggingFace `tokenizer.json` via `--tokenizer`.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m cloud_server_tpu.generate",
        description="Generate text from a trained checkpoint.")
    p.add_argument("--config", help="JSON config with the model section "
                   "used at training time")
    p.add_argument("--checkpoint-dir",
                   help="training checkpoint directory (omit: random init)")
    p.add_argument("--hf-checkpoint", metavar="DIR",
                   help="local HuggingFace LLaMA-family checkpoint "
                   "directory to serve (mutually exclusive with "
                   "--checkpoint-dir; a --config model section may still "
                   "override behavioral fields like dtype/attention_impl — "
                   "structural fields that contradict the checkpoint are "
                   "rejected)")
    p.add_argument("--step", type=int, help="checkpoint step (default latest)")
    p.add_argument("--tokenizer", default="byte",
                   help='"byte" or a local tokenizer.json path')
    p.add_argument("--prompt", action="append", default=[],
                   help="prompt text (repeatable); '-' reads lines from stdin")
    p.add_argument("--max-new", type=int, default=128)
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--top-p", type=float, default=1.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--max-len", type=int, default=0,
                   help="server cache length (default: fits prompt+max-new)")
    p.add_argument("--add-bos", action="store_true",
                   help="prepend BOS to prompts (only if training data "
                   "contained BOS — prepare_corpus does not emit it)")
    p.add_argument("--quantize", action="store_true",
                   help="serve with int8 weight-only quantization (halves "
                   "the weight bytes streamed per decode step)")
    p.add_argument("--kv-cache-int8", action="store_true",
                   help="store the KV cache int8-quantized (halves cache "
                   "memory; the scales fold into the attention math, so "
                   "there is no dequantized cache copy)")
    p.add_argument("--ema", action="store_true",
                   help="serve the EMA-averaged weights from a checkpoint "
                   "trained with ema_decay > 0 (reads the checkpoint's "
                   "'ema' item — one params-sized restore)")
    p.add_argument("--serve-http", type=int, metavar="PORT", default=None,
                   help="instead of batch generation, run the continuous-"
                   "batching server behind an HTTP streaming endpoint "
                   "(POST /generate, ndjson token stream; GET /healthz)")
    p.add_argument("--decode-chunk", type=int, default=1,
                   help="decode steps per scheduler iteration (multi-token "
                   "scheduling; >1 amortises host sync at the cost of "
                   "admission latency)")
    p.add_argument("--max-slots", type=int, default=8,
                   help="concurrent request slots in the server")
    p.add_argument("--spec-drafts", type=int, default=0,
                   help="in-server speculative decoding with N n-gram "
                   "draft tokens per round (exact accept "
                   "rule — output distribution unchanged; wins on "
                   "repetition-heavy output)")
    p.add_argument("--spec-control", metavar="FILE_OR_JSON",
                   default=None,
                   help="adaptive speculative decoding knobs (JSON "
                   "object/string or file path: low/high accept-rate "
                   "hysteresis, ewma, cooldown, probe_period, initial "
                   "draft length — inference/spec_control.py). Omitted: "
                   "the default adaptive controller whenever "
                   "speculation is on; 'off' pins the fixed "
                   "--spec-drafts length")
    p.add_argument("--page-size", type=int, default=128,
                   help="paged server: tokens per KV page (multiple of 128 "
                   "for the pallas decode kernel on TPU)")
    p.add_argument("--num-pages", type=int, default=0,
                   help="paged server: page pool size (0 = the HBM the "
                   "contiguous layout would reserve: "
                   "max_slots * max_context / page_size)")
    p.add_argument("--prefill-chunk", type=int, default=256,
                   help="paged server: admission window width — long "
                   "prompts prefill in chunks this wide, interleaved with "
                   "decode dispatches so inter-token latency stays bounded")
    p.add_argument("--mixed-token-budget", type=int, default=0,
                   help="mixed scheduler: tokens per fused iteration "
                   "(decode rows first, prefill fills the rest; 0 = auto: "
                   "max_slots * (decode window * decode_chunk + "
                   "prefill_chunk), i.e. work-conserving — set lower to "
                   "trade admission speed for a per-iteration ITL bound)")
    p.add_argument("--allocation", choices=["ondemand", "reserve"],
                   default="ondemand",
                   help="paged server page policy: 'ondemand' grows "
                   "chains per dispatch and preempts the youngest slot "
                   "on pool exhaustion (higher concurrency per GB); "
                   "'reserve' pre-reserves prompt+max_new at admission "
                   "(no preemption)")
    p.add_argument("--decode-impl", choices=["xla", "pallas"], default=None,
                   help="decode-attention implementation override; "
                   "'pallas' selects the paged-attention kernel "
                   "(paged server on TPU — length-bounded page reads beat "
                   "the XLA gather on ragged contexts)")
    p.add_argument("--draft-config", metavar="JSON",
                   help="speculative decoding with a small draft model "
                   "sharing the tokenizer (JSON config, model section). "
                   "Batch mode: the standalone speculative batch API. "
                   "--serve-http (paged): IN-SERVER draft-model "
                   "speculation — the draft keeps its own paged cache "
                   "and proposes --num-draft tokens per round")
    p.add_argument("--draft-checkpoint-dir",
                   help="draft model checkpoint (omit: random init — only "
                   "useful for smoke tests)")
    p.add_argument("--num-draft", type=int, default=4,
                   help="draft tokens proposed per speculative round")
    p.add_argument("--access-log", metavar="PATH", nargs="?",
                   const="stderr", default=None,
                   help="with --serve-http: structured JSON access log "
                   "(method, path, status, duration, request id), one "
                   "line per request — to PATH (JSONL file) or, with no "
                   "value, stderr. Off by default.")
    p.add_argument("--profiler-port", type=int, default=None,
                   metavar="PORT",
                   help="with --serve-http: expose the jax profiler "
                   "server on PORT for on-demand remote capture "
                   "(tensorboard profile), alongside the HTTP "
                   "front-end's own POST /debug/trace")
    p.add_argument("--flight-recorder", type=int, default=0,
                   metavar="N",
                   help="paged server: per-iteration flight-recorder "
                   "ring size for /stats post-mortems (0 = config "
                   "default)")
    p.add_argument("--qos-config", metavar="FILE_OR_JSON", default=None,
                   help="multi-tenant QoS: a JSON file path (or inline "
                   "JSON object) declaring per-tenant weights, priority "
                   "classes, token-bucket rate limits, pending bounds, "
                   "and API-key mappings (schema: docs/serving.md). "
                   "Enables weighted fair-share admission, priority "
                   "preemption, and per-tenant 429s; omitted, the "
                   "server runs the byte-identical single-tenant FIFO "
                   "paths")
    p.add_argument("--slo-config", metavar="FILE_OR_JSON", default=None,
                   help="per-priority-class SLO targets: a JSON file "
                   "path (or inline JSON object) declaring per-class "
                   "latency targets (ttft/itl/queue_wait/e2e), "
                   "attainment objectives, and rolling windows (schema: "
                   "inference/slo.py). Surfaced via GET /slo and the "
                   "slo_attainment/slo_burn_rate gauges; omitted, SLO "
                   "tracking is disabled entirely")
    p.add_argument("--fault-plan", metavar="FILE_OR_JSON", default=None,
                   help="deterministic fault injection: a JSON file "
                   "path (or inline JSON object) arming named fault "
                   "sites (submit_reject/dispatch/iteration_stall/"
                   "wedge/alloc_famine) with seeded after/count/p "
                   "windows (schema: inference/faults.py). Proves "
                   "recovery paths — router failover, breakers, "
                   "_fail_all — against a live server; omitted, "
                   "injection is disabled entirely")
    p.add_argument("--brownout", metavar="FILE_OR_JSON", default=None,
                   help="overload brownout (paged server, needs "
                   "--qos-config): a JSON file path (or inline JSON "
                   "object) with OverloadDetector thresholds over "
                   "pending age / budget utilization / host_gap_frac, "
                   "hysteresis, and per-level shed classes (schema: "
                   "inference/faults.py). Sheds best_effort/batch "
                   "admissions with jittered Retry-After 429s before "
                   "the interactive SLO burns")
    p.add_argument("--trace-sample-rate", type=float, default=0.0,
                   metavar="RATE",
                   help="per-request distributed tracing: head-based "
                   "sampling probability in [0, 1]. Sampled requests "
                   "carry span trees (GET /debug/requests/<id>, "
                   "Perfetto export via GET /traces, W3C traceparent "
                   "in/out). 0 (default) disables tracing entirely "
                   "(unless --trace-tail-capacity keeps the recorder "
                   "alive for tail retention)")
    p.add_argument("--trace-capacity", type=int, default=256,
                   metavar="N",
                   help="finished-trace ring size: how many completed "
                   "head-sampled span trees stay inspectable "
                   "(GET /traces; default 256)")
    p.add_argument("--trace-tail-capacity", type=int, default=0,
                   metavar="N",
                   help="tail-based trace retention: keep up to N span "
                   "trees of ANOMALOUS head-unsampled requests "
                   "(failed / deadline-expired / cancelled / migrated "
                   "/ SLO-violating / repeatedly-preempted / finished "
                   "inside an open anomaly window) in a separate ring. "
                   "Works at any --trace-sample-rate, including 0 — "
                   "e.g. 1%% head sampling plus a tail ring means "
                   "broken requests are ALWAYS inspectable. 0 "
                   "(default) disables tail retention")
    p.add_argument("--anomaly-config", metavar="FILE_OR_JSON",
                   default=None,
                   help="anomaly watchdog (inference/anomaly.py): a "
                   "JSON file path (or inline JSON object) tuning the "
                   "rule thresholds (SLO burn rate, TTFT/ITL EWMA "
                   "shift, cache hit-rate collapse, breaker flaps, "
                   "deadline/preemption spikes, host-gap regression, "
                   "wedged scheduler), hysteresis hold, and the "
                   "optional capture_iters/capture_dir auto "
                   "/debug/trace arm. {} enables every rule at "
                   "defaults")
    p.add_argument("--bundle-on-anomaly", action="store_true",
                   help="auto-capture a forensic debug bundle "
                   "(GET /debug/bundle schema) into a bounded ring "
                   "each time a watchdog rule fires (needs "
                   "--anomaly-config)")
    p.add_argument("--no-iteration-profile", action="store_true",
                   help="disable the iteration-phase profiler (on by "
                   "default: per-iteration sweep/admission/build/"
                   "device/commit/epilogue attribution in flight "
                   "records, cloud_server_iter_phase_ms histograms, "
                   "/stats iteration_profile, and the "
                   "GET /debug/scheduler_trace Perfetto export)")
    p.add_argument("--ngram-draft", action="store_true",
                   help="speculative decoding WITHOUT a draft model: "
                   "propose continuations of repeated n-grams from the "
                   "sequence so far (exact output; wins on repetitive "
                   "text); batch mode only")
    from cloud_server_tpu.models.lora import add_lora_args
    add_lora_args(p)
    return p


def load_params(model_cfg, checkpoint_dir: str | None, step: int | None,
                seed: int, loss_fn_module=None, mesh=None):
    """Params-only restore (no optimizer-moment IO), sharded onto `mesh`
    (default: single-device). Random-inits when no checkpoint_dir."""
    import jax

    from cloud_server_tpu.config import MeshConfig
    from cloud_server_tpu.models import transformer
    from cloud_server_tpu.parallel.mesh import make_mesh

    if loss_fn_module is None:
        loss_fn_module = transformer
    if checkpoint_dir is None:
        print("[generate] no --checkpoint-dir; using random init",
              file=sys.stderr)
        return loss_fn_module.init_params(model_cfg, jax.random.key(seed))

    from cloud_server_tpu.training.checkpoint import restore_params
    mesh = mesh if mesh is not None else make_mesh(MeshConfig())
    return restore_params(checkpoint_dir, model_cfg, mesh, step=step,
                          loss_fn_module=loss_fn_module)


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)

    from cloud_server_tpu.utils.platform import (
        device_line, enable_compile_cache)
    enable_compile_cache()
    device_line("generate")

    from cloud_server_tpu.config import InferConfig, ModelConfig, from_json
    from cloud_server_tpu.data.tokenizer import get_tokenizer

    raw = {}
    if args.config:
        with open(args.config) as f:
            raw = json.load(f)
    hf_params = None
    if args.hf_checkpoint:
        if args.checkpoint_dir:
            raise SystemExit(
                "--hf-checkpoint and --checkpoint-dir are mutually "
                "exclusive")
        if args.step is not None:
            raise SystemExit("--step does not apply to --hf-checkpoint")
        from cloud_server_tpu.models.lora import lora_config_from_args
        if lora_config_from_args(args) is not None:
            raise SystemExit(
                "--lora-* flags do not apply to --hf-checkpoint (merge "
                "adapters into an HF checkpoint first, or train from a "
                "framework checkpoint)")
        from cloud_server_tpu.models.hf_convert import load_hf_checkpoint
        model_cfg, hf_params = load_hf_checkpoint(
            args.hf_checkpoint, **raw.get("model", {}))
    else:
        model_cfg = from_json(ModelConfig, raw.get("model", {}))
    if args.kv_cache_int8:
        model_cfg = dataclasses.replace(model_cfg, kv_cache_dtype="int8")
    if args.decode_impl is not None:
        model_cfg = dataclasses.replace(
            model_cfg, decode_attention_impl=args.decode_impl)
    if args.spec_drafts and args.ngram_draft:
        raise SystemExit(
            "--spec-drafts (in-server n-gram) and --ngram-draft (batch "
            "API) are mutually exclusive speculation paths")
    tok = get_tokenizer(args.tokenizer)
    if tok.vocab_size > model_cfg.vocab_size:
        raise SystemExit(
            f"tokenizer vocab ({tok.vocab_size}) exceeds model vocab "
            f"({model_cfg.vocab_size})")

    prompts = []
    for prm in args.prompt:
        if prm == "-":
            prompts.extend(line.rstrip("\n") for line in sys.stdin)
        else:
            prompts.append(prm)
    if not prompts and args.serve_http is None:
        raise SystemExit("no prompts (use --prompt, repeatable, or '-')")

    from cloud_server_tpu.models.lora import (
        export_merged, load_lora_config, lora_config_from_args,
        make_lora_module)
    lcfg = lora_config_from_args(args)
    if args.checkpoint_dir:
        saved = load_lora_config(args.checkpoint_dir)
        if saved is not None:
            # the sidecar written at training time is authoritative: a
            # mismatched alpha would silently rescale the adapters
            if lcfg is not None and lcfg != saved:
                raise SystemExit(
                    f"--lora-* flags {lcfg} contradict the checkpoint's "
                    f"recorded LoRA config {saved}; drop the flags (the "
                    "sidecar is used automatically)")
            lcfg = saved
    if args.ema:
        if hf_params is not None or lcfg is not None:
            raise SystemExit("--ema applies to framework checkpoints "
                             "without LoRA flags")
        if not args.checkpoint_dir:
            raise SystemExit("--ema needs --checkpoint-dir")
        from cloud_server_tpu.config import MeshConfig
        from cloud_server_tpu.models import transformer
        from cloud_server_tpu.parallel.mesh import make_mesh
        from cloud_server_tpu.training.checkpoint import restore_ema_params
        moe_module = None
        if model_cfg.num_experts >= 2:
            from cloud_server_tpu.models import moe as moe_module
        try:
            params = restore_ema_params(
                args.checkpoint_dir, model_cfg, make_mesh(MeshConfig()),
                step=args.step, loss_fn_module=moe_module or transformer)
        except FileNotFoundError as e:
            raise SystemExit(str(e))
    elif hf_params is not None:
        params = hf_params
    elif lcfg is not None:
        base_module = transformer
        if model_cfg.num_experts >= 2:
            from cloud_server_tpu.models import moe as base_module
        lora_module = make_lora_module(lcfg, base_module=base_module)
        params = load_params(model_cfg, args.checkpoint_dir, args.step,
                             args.seed, loss_fn_module=lora_module)
        params = export_merged(params, lcfg, base_module=base_module)
    else:
        moe_module = None
        if model_cfg.num_experts >= 2:
            from cloud_server_tpu.models import moe as moe_module
        params = load_params(model_cfg, args.checkpoint_dir, args.step,
                             args.seed, loss_fn_module=moe_module)
    if args.quantize:
        from cloud_server_tpu.models.quantization import quantize_params
        params = quantize_params(params)
    infer_cfg = InferConfig(
        max_decode_len=args.max_new, temperature=args.temperature,
        top_k=args.top_k, top_p=args.top_p,
        eos_token_id=tok.eos_id if tok.eos_id is not None else -1,
        pad_token_id=tok.pad_id or 0,
        trace_capacity=args.trace_capacity,
        trace_tail_capacity=args.trace_tail_capacity,
        bundle_on_anomaly=args.bundle_on_anomaly)

    def load_draft():
        """Draft model for in-server speculation (--draft-config).
        Returns (params, cfg) or (None, None)."""
        if not args.draft_config:
            return None, None
        with open(args.draft_config) as f:
            draft_cfg = from_json(ModelConfig, json.load(f).get("model", {}))
        draft_module = None
        if draft_cfg.num_experts >= 2:
            from cloud_server_tpu.models import moe as draft_module
        draft_params = load_params(draft_cfg, args.draft_checkpoint_dir,
                                   None, args.seed + 1,
                                   loss_fn_module=draft_module)
        return draft_params, draft_cfg

    def make_server(max_len: int, max_slots: int):
        """Build the server (submit / generate / start / stop)."""
        ps = args.page_size
        max_context = -(-max_len // ps) * ps  # round up to a page multiple
        prefill_chunk = -(-max(ps, args.prefill_chunk) // ps) * ps
        draft_params, draft_cfg = load_draft()
        spec = args.spec_drafts
        if draft_cfg is not None and spec == 0:
            spec = args.num_draft  # --draft-config implies speculation
        from cloud_server_tpu.inference.paged_server import (
            PagedInferenceServer)
        return PagedInferenceServer(
            params, model_cfg, infer_cfg, max_slots=max_slots,
            max_context=max_context, page_size=ps,
            num_pages=args.num_pages or None,
            decode_chunk=args.decode_chunk,
            spec_drafts=spec,
            spec_control=args.spec_control,
            prefill_chunk=prefill_chunk, seed=args.seed,
            allocation=args.allocation,
            mixed_token_budget=args.mixed_token_budget,
            flight_recorder_size=args.flight_recorder or None,
            draft_params=draft_params, draft_cfg=draft_cfg,
            qos=args.qos_config,
            slo=args.slo_config,
            tracing=args.trace_sample_rate or None,
            faults=args.fault_plan,
            brownout=args.brownout,
            anomaly=args.anomaly_config,
            iteration_profile=False if args.no_iteration_profile else None,
            tokenizer=tok)  # regex-constrained requests compile vs it

    if args.serve_http is not None:
        if args.ngram_draft:
            raise SystemExit(
                "--ngram-draft is batch-mode only (the serving "
                "equivalent is --spec-drafts)")
        from cloud_server_tpu.inference.http_server import HttpFrontend
        max_len = args.max_len or model_cfg.max_seq_len
        srv = make_server(max_len, args.max_slots).start()
        access_log = (True if args.access_log == "stderr"
                      else args.access_log)
        front = HttpFrontend(srv, tokenizer=tok, port=args.serve_http,
                             access_log=access_log)
        front.start()
        if args.profiler_port is not None:
            from cloud_server_tpu.utils.tracing import (
                start_profiler_server)
            start_profiler_server(args.profiler_port)
            print(f"[generate] jax profiler server on port "
                  f"{args.profiler_port}", file=sys.stderr)
        host, port = front.address
        print(f"[generate] serving on http://{host}:{port} — try:\n"
              f"  curl -N -s {host}:{port}/generate "
              "-d '{\"prompt\": \"hello\"}'", file=sys.stderr)
        try:
            import signal
            signal.pause()
        except (KeyboardInterrupt, AttributeError):
            pass
        finally:
            front.stop()
            srv.stop()
        return

    encoded = [tok.encode(p, add_bos=args.add_bos and tok.bos_id is not None)
               or [0] for p in prompts]
    if args.draft_config or args.ngram_draft:
        import jax
        import numpy as np

        from cloud_server_tpu.inference.speculative import (
            speculative_generate)
        if args.quantize:
            raise SystemExit("--quantize + speculative decoding not "
                             "supported yet")
        if args.draft_config and args.ngram_draft:
            raise SystemExit("--draft-config and --ngram-draft are "
                             "mutually exclusive draft sources")
        draft_cfg = draft_params = None
        if args.draft_config:
            with open(args.draft_config) as f:
                draft_cfg = from_json(ModelConfig,
                                      json.load(f).get("model", {}))
            draft_module = None
            if draft_cfg.num_experts >= 2:
                from cloud_server_tpu.models import moe as draft_module
            draft_params = load_params(draft_cfg, args.draft_checkpoint_dir,
                                       None, args.seed + 1,
                                       loss_fn_module=draft_module)
        longest = max(len(e) for e in encoded)
        # honour --max-len / the trained context window like the plain
        # path: the cache must hold prompt + new tokens + the speculative
        # window's overhang, so clamp max_new to what fits.
        cap = args.max_len or model_cfg.max_seq_len
        budget = cap - longest - args.num_draft - 1
        if budget < 1:
            raise SystemExit(
                f"prompt ({longest}) + speculative window "
                f"({args.num_draft + 1}) leaves no room to decode within "
                f"max_len={cap}; raise --max-len or shorten the prompt")
        max_new = min(args.max_new, budget)
        if max_new < args.max_new:
            print(f"[generate] clamping --max-new {args.max_new} -> "
                  f"{max_new} to fit max_len={cap}", file=sys.stderr)
            infer_cfg = dataclasses.replace(infer_cfg,
                                            max_decode_len=max_new)
        padded = np.zeros((len(encoded), longest), np.int32)
        lengths = np.asarray([len(e) for e in encoded], np.int32)
        for i, e in enumerate(encoded):
            padded[i, :len(e)] = e
        toks = speculative_generate(
            params, draft_params, jax.numpy.asarray(padded),
            jax.random.key(args.seed), cfg=model_cfg, draft_cfg=draft_cfg,
            infer_cfg=infer_cfg, num_draft=args.num_draft,
            max_len=longest + max_new + args.num_draft + 1,
            prompt_lengths=jax.numpy.asarray(lengths))
        for prompt, row in zip(prompts, np.asarray(toks)):
            row = list(row)
            if infer_cfg.eos_token_id >= 0 and infer_cfg.eos_token_id in row:
                row = row[:row.index(infer_cfg.eos_token_id)]
            # only TRAILING pads are padding; a mid-stream token that
            # happens to equal pad_token_id is real output (byte 0 for the
            # byte tokenizer) and the plain path prints it
            while row and row[-1] == infer_cfg.pad_token_id:
                row.pop()
            print(f"=== {prompt!r}")
            print(tok.decode(row))
        return

    longest = max(len(e) for e in encoded)
    max_len = args.max_len or min(model_cfg.max_seq_len,
                                  longest + args.max_new +
                                  args.spec_drafts + 1)
    srv = make_server(max_len, min(args.max_slots, len(encoded)))
    outs = srv.generate(encoded, max_new_tokens=args.max_new)
    for prompt, out in zip(prompts, outs):
        print(f"=== {prompt!r}")
        print(tok.decode(out))


if __name__ == "__main__":
    main()

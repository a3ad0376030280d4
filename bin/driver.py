#!/usr/bin/env python
"""Training driver CLI — the entry point for single- and multi-host runs.

TPU-native replacement for the reference's driver pair (bin/driver.jl +
bin/main.jl): where the reference `addprocs(4)`s worker processes, parses
the sample table on process 1, hand-builds two sets of capacity-1
RemoteChannels and calls `FluxDistributed.start` (bin/driver.jl:3-41),
here ONE command runs on every host of a pod slice (or alone on a dev
box):

    # single host (all local chips):
    python bin/driver.py --model resnet50 --dataset synthetic \
        --batch-size 256 --cycles 100

    # each host of a TPU pod slice (cluster auto-detected):
    python bin/driver.py --model resnet50 --dataset imagenet ...

    # manual bring-up (e.g. CPU fake cluster):
    python bin/driver.py --coordinator localhost:9999 \
        --num-processes 2 --process-id $I --platform cpu --local-devices 4 ...

The compiled SPMD step is identical in every mode — multi-host changes
only device enumeration, not the program (contrast with the reference's
two separate code paths, src/ddp_tasks.jl vs src/sync.jl).
"""

from __future__ import annotations

import argparse
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--model", default="resnet50",
                   help="model factory name in fluxdistributed_tpu.models "
                        "(resnet18/34/50/101/152, ...)")
    p.add_argument("--num-classes", type=int, default=None,
                   help="override class count (default: dataset's)")
    p.add_argument("--dataset", default="synthetic",
                   help="registered dataset name (Data.toml analog), 'synthetic' "
                        "(images), or 'synthetic-text' (LM token stream)")
    p.add_argument("--vocab", type=int, default=256,
                   help="vocab size for lm_* models / synthetic-text")
    p.add_argument("--seqlen", type=int, default=128,
                   help="sequence length for synthetic-text")
    p.add_argument("--data-toml", default=None,
                   help="dataset registry TOML to load (Data.toml analog)")
    p.add_argument("--val-dataset", default=None, help="registered val dataset name")
    p.add_argument("--image-size", type=int, default=224,
                   help="synthetic image side (smoke/test runs use small sizes)")
    p.add_argument("--batch-size", type=int, default=256,
                   help="GLOBAL batch size (reference: 96/device x N, README.md:43)")
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--cycles", type=int, default=None,
                   help="explicit cycle count (overrides epochs)")
    p.add_argument("--opt", default="momentum", choices=["momentum", "nesterov", "adam", "adamw", "descent", "lars"],
                   help="optimizer (reference: Momentum(0.01,0.9) README.md:37; ADAM src/sync.jl:97)")
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--warmup-steps", type=int, default=0)
    p.add_argument("--total-steps", type=int, default=None,
                   help="enable warmup-cosine schedule to this horizon")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=20,
                   help="cycles between checkpoints (reference: 20, src/sync.jl:156)")
    p.add_argument("--resume", action="store_true",
                   help="resume from latest checkpoint in --checkpoint-dir")
    p.add_argument("--print-every", type=int, default=10)
    p.add_argument("--eval-every", type=int, default=50)
    p.add_argument("--final-eval", action="store_true",
                   help="after training, aggregate loss/top-k over the FULL "
                        "--val-dataset with train.evaluate")
    p.add_argument("--spmd", default="jit",
                   choices=["jit", "dp", "shard_map",
                            "pp", "pp_1f1b", "ep", "sp"])
    p.add_argument("--layout", default=None, metavar="NAME|auto",
                   help="declarative dp x fsdp x tp layout "
                        "(parallel/layout.py): a preset name (dp, fsdp, "
                        "tp, dp_fsdp, fsdp_tp, dp_fsdp_tp) shards the "
                        "model from its committed rule table + the fsdp "
                        "overlay — NO per-model spec code; 'auto' runs "
                        "the layout picker (prices every candidate's "
                        "real compiled step, ranks by HBM headroom via "
                        "the fit checker's ranking, breaks ties by the "
                        "collective ledger) and trains with the fastest "
                        "layout that fits.  Keep --spmd jit (default)")
    p.add_argument("--hbm-bytes", type=float, default=None,
                   help="per-device HBM budget in bytes for --layout "
                        "auto (default: the live device bytes_limit; "
                        "REQUIRED for fit verdicts on backends without "
                        "memory_stats, e.g. the CPU mesh)")
    p.add_argument("--layout-report", default=None, metavar="PATH",
                   help="write the layout picker's report (chosen "
                        "layout + per-candidate headroom/ledger "
                        "ranking) as JSON here (--layout auto)")
    p.add_argument("--zero1", action="store_true",
                   help="ZeRO-1 weight-update sharding for the DP paths "
                        "(--spmd jit/dp/shard_map): reduce-scatter grads, "
                        "shard the optimizer state and update 1/N over the "
                        "data axis, all-gather updated params — DP-identical "
                        "numerics, ~N x lower optimizer memory")
    p.add_argument("--steps-per-call", type=int, default=1,
                   help="optimizer steps per dispatch (device loop; spmd=jit). "
                        "Amortizes host dispatch latency")
    p.add_argument("--tp", type=int, default=None,
                   help="model-axis size for --layout tp / fsdp_tp (the "
                        "layout becomes dp=N/tp x tp, resp. fsdp=N/tp x "
                        "tp; default: the preset's own split)")
    p.add_argument("--pipe", type=int, default=None,
                   help="pipe-axis size for --spmd pp / pp_1f1b (mesh "
                        "becomes {data: N/pipe, pipe: pipe}; defaults to "
                        "all devices, i.e. data=1)")
    p.add_argument("--microbatches", type=int, default=None,
                   help="pipeline microbatches per step (default 2x pipe "
                        "size; the (S-1)/(M+S-1) bubble shrinks as M grows)")
    p.add_argument("--pp-interleave", action="store_true",
                   help="Megatron interleaved virtual stages for --spmd "
                        "pp_1f1b (depth/pipe chunks per device; ~V-fold "
                        "smaller fill/drain bubble)")
    p.add_argument("--pp-schedule", default="1f1b", choices=["1f1b", "zb"],
                   help="pipeline timetable for --spmd pp_1f1b: classic "
                        "1F1B, or 'zb' (zero-bubble ZB-H1: each backward "
                        "splits into input-grad + deferred weight-grad "
                        "ticks and the weight-grad work fills the drain "
                        "bubble; bit-identical gradients)")
    p.add_argument("--pp-plan", default=None, metavar="PATH|auto",
                   help="profile-guided stage placement for --spmd "
                        "pp/pp_1f1b: 'auto' stages the model out and "
                        "plans from fresh static costs; PATH loads a "
                        "cost-profile artifact (--profile-out output) or "
                        "a saved plan JSON — non-uniform stage boundaries "
                        "minimizing the modeled max-stage cost (also "
                        "lifts the depth %% pipe divisibility "
                        "requirement).  Cross-topology artifacts are "
                        "rejected via the fingerprint check")
    p.add_argument("--expert-parallel", type=int, default=None,
                   help="expert-axis size for --spmd ep (mesh becomes "
                        "{data: N/ep, expert: ep}; defaults to all devices)")
    p.add_argument("--experts", type=int, default=None,
                   help="number of MoE experts for --spmd ep (multiple of "
                        "the expert axis; defaults to the axis size)")
    p.add_argument("--moe-every", type=int, default=None,
                   help="route every K-th decoder block through the MoE "
                        "layer (--spmd ep; default 2)")
    p.add_argument("--attn", default="dense",
                   choices=["dense", "blockwise", "flash"],
                   help="attention core for lm_* models: XLA dense, XLA "
                        "blockwise (memory-bounded scan), or the Pallas "
                        "flash kernel (fused fwd+bwd). Not combinable with "
                        "--spmd sp, which picks its own context-parallel "
                        "attention")
    p.add_argument("--attn-block", type=int, default=None,
                   help="block size for --attn blockwise|flash (default 128)")
    p.add_argument("--kv-heads", type=int, default=None,
                   help="grouped-query attention for lm_* models: number "
                        "of KV heads (must divide the model's num_heads; "
                        "shrinks the KV cache by num_heads/kv_heads)")
    p.add_argument("--window", type=int, default=None,
                   help="sliding-window attention for lm_* models: each "
                        "query attends its WINDOW newest keys (O(T*W) "
                        "attention; with --attn flash, out-of-band KV "
                        "blocks are skipped entirely)")
    p.add_argument("--sinks", type=int, default=0,
                   help="StreamingLLM attention sinks for lm_* models: the "
                        "first SINKS keys stay attendable outside the "
                        "window (requires --window)")
    p.add_argument("--norm", default="layernorm",
                   choices=["layernorm", "rmsnorm"],
                   help="lm_* block norm (rmsnorm = Llama-style)")
    p.add_argument("--mlp", default="gelu", choices=["gelu", "swiglu"],
                   help="lm_* block MLP (swiglu = Llama-style gated)")
    p.add_argument("--sp-strategy", default="ring",
                   choices=["ring", "ulysses"],
                   help="context-parallel attention for --spmd sp: 'ring' "
                        "(ppermute KV rotation, O(T/P) memory, any head "
                        "count) or 'ulysses' (two all_to_alls re-shard "
                        "seq<->heads; needs num_heads %% seq-axis == 0)")
    p.add_argument("--seq-parallel", type=int, default=None,
                   help="seq-axis size for --spmd sp (mesh becomes "
                        "{data: N/sp, seq: sp}; the LM runs ring attention "
                        "with the sequence sharded across it; defaults to "
                        "all devices)")
    p.add_argument("--verbose", action="store_true")
    p.add_argument("--wandb", action="store_true", help="log to Weights & Biases")
    # observability (fluxdistributed_tpu.obs): live endpoints + traces
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve GET /metrics (Prometheus text: step counter, "
                        "per-phase histograms, compile counts, OOM skips, "
                        "prefetch depth) and GET /healthz on this port for "
                        "the duration of the run (coordinator host only — "
                        "the serve/server.py stdlib-HTTP pattern)")
    p.add_argument("--trace-events", default=None, metavar="PATH",
                   help="write the step timeline (every loader item's item/"
                        "data_wait/dispatch/device/assemble/h2d spans, plus "
                        "compile/eval/checkpoint; always recorded, in a "
                        "bounded ring) as Chrome/Perfetto trace-event JSON "
                        "here at exit; the loop runs as it does without")
    p.add_argument("--metrics-jsonl", default=None, metavar="PATH",
                   help="append registry snapshots (JSON lines) here at the "
                        "print cadence — offline run diffing without a "
                        "Prometheus server")
    p.add_argument("--profile-out", default=None, metavar="PATH",
                   help="write a versioned cost-profile artifact "
                        "(obs.profile schema: static per-layer/step "
                        "FLOPs+bytes from the staged-out program, the "
                        "run's measured phase histograms, topology "
                        "fingerprint) here when training ends — the "
                        "input the pipeline planner and "
                        "benchmarks/pp_bubble.py consume")
    p.add_argument("--steady-after", type=int, default=None, metavar="N",
                   help="declare XLA warmup over after N cycles: any later "
                        "compile is counted + warned as a steady-state "
                        "recompile (fdtpu_jax_steady_recompiles_total)")
    p.add_argument("--flight", default=None, metavar="PATH",
                   help="black-box flight recorder (obs.flight): append "
                        "per-step records (step, loss, guard verdict, "
                        "phase seconds, headroom, compiles) here, flushed "
                        "+ checkpointed every few records — a SIGKILL "
                        "loses at most one flush interval, and the dump "
                        "footer (or its absence) says how the run ended")
    p.add_argument("--runs-ledger", default=None, metavar="PATH",
                   help="append one cross-run ledger record (obs.runs "
                        "schema: status, topology fingerprint, steps, "
                        "compile seconds, flight-dump path) here on every "
                        "exit path — the history bin/trends.py gates "
                        "regressions against")
    # cold-start performance (fluxdistributed_tpu.compilation)
    p.add_argument("--compile-cache", default=None, metavar="DIR",
                   help="directory of JAX's persistent compilation "
                        "cache (always on: the next run on the same "
                        "topology reads its XLA compiles from disk). "
                        "JAX_COMPILATION_CACHE_DIR wins when set; "
                        "default: .jax_cache/ in the checkout")
    p.add_argument("--aot", default=None, metavar="DIR",
                   help="serialized train-step executables: load the "
                        "compiled step from DIR when topology + argument "
                        "signature match, else compile at prepare time "
                        "and serialize for the next process (also skips "
                        "tracing/lowering, which the compile cache "
                        "cannot)")
    p.add_argument("--prewarm", action="store_true",
                   help="run one donated dummy train step (and eval, "
                        "when a val set exists) before the training loop "
                        "starts, so step-0 timing excludes compilation")
    p.add_argument("--strict-checks", action="store_true",
                   help="debug-grade first steps: call 1 runs under "
                        "jax_debug_nans (a NaN names its producing "
                        "primitive), call 2 under "
                        "jax.transfer_guard('disallow') (an implicit "
                        "host<->device transfer on the steady state "
                        "raises); failures name the offending phase")
    p.add_argument("--watchdog-factor", type=float, default=5.0,
                   help="stall watchdog threshold as a multiple of the "
                        "rolling-median step time (warns + flips /healthz "
                        "to 503 when no step lands inside it; eval and "
                        "checkpoint phases are exempt). 0 disables the "
                        "watchdog")
    p.add_argument("--watchdog-escalate", type=int, default=4, metavar="N",
                   help="after a stall persists N further threshold "
                        "windows with no step, count a watchdog "
                        "ESCALATION (fdtpu_watchdog_escalations_total) — "
                        "the wedged-collective signal bin/supervise.py "
                        "SIGKILLs on. 0 disables escalation")
    # self-healing guard (fluxdistributed_tpu/train/guard.py)
    p.add_argument("--guard", action="store_true",
                   help="self-healing training: compile the anomaly "
                        "sentinel into the train step (global isfinite "
                        "any-reduce over loss+grads and global grad-norm, "
                        "ONE extra scalar fetch per step) and arm the "
                        "policy ladder — quarantine-and-skip anomalous "
                        "batches, roll back to the last-good checkpoint "
                        "when anomalies persist, halt (exit rc 65, not "
                        "retryable) when rollbacks loop.  Decisions are "
                        "recorded in the RESUME manifest and visible as "
                        "fdtpu_guard_* metrics")
    p.add_argument("--guard-zmax", type=float, default=8.0,
                   help="robust z-score above which a finite loss counts "
                        "as a spike anomaly")
    p.add_argument("--guard-window", type=int, default=64,
                   help="rolling window (accepted losses) behind the "
                        "spike detector's median/MAD")
    p.add_argument("--guard-rollback-after", type=int, default=3,
                   help="anomalies within the guard's anomaly window "
                        "that escalate skip -> rollback")
    p.add_argument("--replay-step", type=int, default=None, metavar="K",
                   help="diagnosis harness: instead of training, "
                        "re-execute loader item K deterministically "
                        "(same (seed, process, item) batch derivation) "
                        "against the prepared — or, with --resume, the "
                        "restored — state under jax_debug_nans, print "
                        "one JSON report line and exit.  The postmortem "
                        "for a quarantined step")
    p.add_argument("--fault-plan", default=None, metavar="JSON",
                   help="install a deterministic fault-injection plan "
                        "(fluxdistributed_tpu.faults) before anything "
                        "else runs — chaos/testing harness.  JSON object "
                        "or @path/to/plan.json, e.g. "
                        "'{\"sigterm_at_step\": 50}' proves the "
                        "checkpoint-on-SIGTERM path, "
                        "'{\"params\": {\"local_devices\": 4}}' simulates "
                        "a device-count change on resume")
    # manual cluster bring-up (CPU fake cluster / debugging)
    p.add_argument("--coordinator", default=None, help="coordinator host:port")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument("--platform", default=None, help="force platform (e.g. cpu)")
    p.add_argument("--local-devices", type=int, default=None,
                   help="virtual CPU devices per process (fake-cluster mode)")
    return p


def _resolve_pp_plan(args, model, mesh):
    """``--pp-plan``: 'auto' stages the model out for fresh static
    costs; a path loads a cost-profile artifact (planned here) or a
    saved plan JSON (planned elsewhere) — the shared
    ``parallel.pp_plan.resolve_plan`` implementation, which rejects
    cross-topology artifacts through the fingerprint check
    (``prepare_training`` re-checks at consume time too)."""
    from fluxdistributed_tpu import mesh as mesh_lib
    from fluxdistributed_tpu.obs.profile import ProfileMismatch
    from fluxdistributed_tpu.parallel.pp_plan import PlanError, resolve_plan

    S = mesh.shape[mesh_lib.PIPE_AXIS]
    n_data = mesh.shape[mesh_lib.DATA_AXIS]
    M = args.microbatches or 2 * S
    try:
        return resolve_plan(
            args.pp_plan, S, M,
            schedule=args.pp_schedule if args.spmd == "pp_1f1b" else "1f1b",
            model=model,
            batch_size=max(args.batch_size // max(n_data, 1), 1),
            seqlen=args.seqlen)
    except (PlanError, ProfileMismatch, ValueError, OSError) as e:
        raise SystemExit(f"--pp-plan {args.pp_plan}: {e}")


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    from fluxdistributed_tpu import faults

    if args.fault_plan:
        import json

        spec = args.fault_plan
        if spec.startswith("@"):
            with open(spec[1:]) as f:
                spec = f.read()
        faults.install_plan(faults.FaultPlan.from_spec(json.loads(spec)))
        # a plan can simulate a device-count change on resume: the next
        # grant window handing back a different slice is modeled by
        # overriding the virtual-device count before backend init
        override = faults.param("local_devices")
        if override is not None:
            args.local_devices = int(override)
            args.platform = args.platform or "cpu"

    # Distributed init MUST precede any backend use.
    from fluxdistributed_tpu.parallel import multihost

    multihost.initialize(
        coordinator_address=args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
        platform=args.platform,
        local_devices=args.local_devices,
    )

    import jax

    import fluxdistributed_tpu as fd
    from fluxdistributed_tpu import compilation, models, optim
    from fluxdistributed_tpu.data import SyntheticDataset
    from fluxdistributed_tpu.train import prepare_training, train
    from fluxdistributed_tpu.train.logging import ConsoleLogger, NullLogger

    if args.data_toml:
        fd.load_registry(args.data_toml)

    if args.dataset == "synthetic":
        dataset = SyntheticDataset(nsamples=max(args.batch_size * 8, 1024),
                                   nclasses=args.num_classes or 1000,
                                   shape=(args.image_size, args.image_size, 3))
    elif args.dataset == "synthetic-text":
        from fluxdistributed_tpu.data import SyntheticTextDataset

        dataset = SyntheticTextDataset(vocab=args.vocab, seqlen=args.seqlen)
    elif args.dataset.startswith("text:"):
        # byte-level LM on any local file: --dataset text:/path/corpus.txt
        from fluxdistributed_tpu.data import ByteTextDataset

        dataset = ByteTextDataset(args.dataset[len("text:"):], seqlen=args.seqlen)
        args.vocab = dataset.vocab
    else:
        dataset = fd.open_dataset(args.dataset)
    val_dataset = fd.open_dataset(args.val_dataset) if args.val_dataset else None

    model_fn = getattr(models, args.model)
    is_lm = args.model.startswith("lm_") or args.model == "TransformerLM"
    if not is_lm and not hasattr(dataset, "nclasses"):
        raise SystemExit(
            f"--dataset {args.dataset} is a token stream; use an lm_* model"
        )
    if is_lm and hasattr(dataset, "nclasses"):
        raise SystemExit(
            f"--model {args.model} trains on tokens; use --dataset synthetic-text"
        )
    if args.final_eval and args.val_dataset is None:
        raise SystemExit("--final-eval needs --val-dataset")
    def data_x_mesh(axis: str, flag: str, requested):
        """The shared {data: N/k, <axis>: k} mesh recipe behind --pipe /
        --expert-parallel / --seq-parallel: resolve the default
        (all devices), validate divisibility, build the mesh."""
        from fluxdistributed_tpu.mesh import make_mesh

        ndev = jax.device_count()
        k = requested if requested is not None else ndev
        if k < 2 or ndev % k:
            raise SystemExit(
                f"{flag} {k} must be >=2 and divide {ndev} devices")
        return make_mesh({"data": ndev // k, axis: k}), k

    # a named layout resolves before the model is built (its model-axis
    # size bounds --kv-heads); 'auto' is picked once the model exists
    chosen = None
    if args.tp is not None and args.layout not in ("tp", "fsdp_tp"):
        raise SystemExit("--tp only applies with --layout tp or fsdp_tp")
    if args.layout not in (None, "auto"):
        from fluxdistributed_tpu.parallel import layout as layout_lib

        ndev = jax.device_count()
        if args.tp is None:
            try:
                chosen = layout_lib.resolve_layout(args.layout)
            except layout_lib.LayoutError as e:
                raise SystemExit(f"--layout {args.layout}: {e}")
        elif args.tp < 1 or ndev % args.tp:
            raise SystemExit(
                f"--tp {args.tp} must be >=1 and divide {ndev} devices")
        elif args.layout == "tp":
            chosen = layout_lib.Layout("tp", dp=ndev // args.tp, tp=args.tp)
        elif args.tp == ndev:
            raise SystemExit(
                "--layout fsdp_tp needs --tp < device count: with no fsdp "
                "extent there is nothing for FSDP to shard over")
        else:
            chosen = layout_lib.Layout(
                "fsdp_tp", fsdp=ndev // args.tp, tp=args.tp)

    # Sequence/context parallelism: the model's attn_fn closes over the
    # mesh, so the seq mesh is built BEFORE the model for this mode
    sp_mesh = None
    sp_kwargs = {}
    if args.spmd == "sp":
        from fluxdistributed_tpu.parallel import (
            make_ring_attention, make_ulysses_attention,
        )

        if not is_lm:
            raise SystemExit("--spmd sp needs an lm_* model (causal context-"
                             "parallel attention over the sequence)")
        sp_mesh, sp = data_x_mesh("seq", "--seq-parallel", args.seq_parallel)
        if args.seqlen % sp:
            raise SystemExit(f"--seqlen {args.seqlen} must be a multiple of "
                             f"the seq axis size {sp}")
        if args.sp_strategy == "ulysses":
            # Ulysses re-shards heads over the seq axis: the head count is
            # a model-constructor default, so probe it before committing.
            nheads = model_fn(vocab=args.vocab).num_heads
            if nheads % sp:
                raise SystemExit(
                    f"--sp-strategy ulysses needs num_heads ({nheads} for "
                    f"{args.model}) divisible by the seq axis size {sp}; "
                    f"use --seq-parallel accordingly or --sp-strategy ring")
            make_attn = make_ulysses_attention
        else:
            make_attn = make_ring_attention
        sp_kwargs = {"attn_fn": make_attn(
            sp_mesh, batch_axis="data", causal=True)}

    # Attention-core selection for the LM family (one flag, shared
    # wiring with benchmarks/lm_bench.py via ops.attention_core)
    attn_kwargs = {}
    if args.attn_block is not None and args.attn == "dense":
        raise SystemExit("--attn-block only applies with --attn "
                         "blockwise|flash")
    if args.attn_block is not None and args.attn_block <= 0:
        raise SystemExit(f"--attn-block must be > 0, got {args.attn_block}")
    if args.window is not None:
        if not is_lm:
            raise SystemExit("--window only applies to lm_* models")
        if args.window < 1:
            raise SystemExit(f"--window must be >= 1, got {args.window}")
        if args.spmd == "sp":
            raise SystemExit("--window is not supported with --spmd sp "
                             "(context-parallel attention is unwindowed)")
        # the model field windows the default dense core AND the decode
        # path; a non-dense attn_fn gets its own window below
        attn_kwargs["window"] = args.window
        if args.sinks:
            if args.sinks < 0:
                raise SystemExit(f"--sinks must be >= 0, got {args.sinks}")
            attn_kwargs["sinks"] = args.sinks
    if args.sinks and args.window is None:
        raise SystemExit("--sinks requires --window")
    if args.attn != "dense":
        from fluxdistributed_tpu.ops import attention_core

        if not is_lm:
            raise SystemExit("--attn only applies to lm_* models")
        if args.spmd == "sp":
            raise SystemExit("--attn conflicts with --spmd sp: sequence "
                             "parallelism picks its own attention core "
                             "(use --sp-strategy)")
        attn_kwargs["attn_fn"] = attention_core(
            args.attn, args.attn_block if args.attn_block else 128,
            window=args.window, sinks=args.sinks)
    if args.kv_heads is not None:
        if not is_lm:
            raise SystemExit("--kv-heads only applies to lm_* models")
        nheads = model_fn(vocab=args.vocab).num_heads
        if args.kv_heads <= 0 or nheads % args.kv_heads:
            raise SystemExit(
                f"--kv-heads {args.kv_heads} must be > 0 and divide the "
                f"model's num_heads ({nheads} for {args.model})")
        if chosen is not None and args.kv_heads % chosen.tp:
            # the lm_tp table head-shards the kv projection: the model
            # axis must divide the KV head count
            raise SystemExit(
                f"--kv-heads {args.kv_heads} must be a multiple of the "
                f"layout's model-axis size ({chosen.tp}) so the grouped "
                f"kv projection can be head-sharded")
        attn_kwargs["num_kv_heads"] = args.kv_heads
    if args.norm != "layernorm" or args.mlp != "gelu":
        if not is_lm:
            raise SystemExit("--norm/--mlp only apply to lm_* models")
        attn_kwargs["norm"] = args.norm
        attn_kwargs["mlp"] = args.mlp

    # MoE expert parallelism: the model's moe_fn closes over the mesh,
    # so the expert mesh is built BEFORE the model for this mode
    ep_mesh = None
    moe_kwargs = {}
    if args.spmd == "ep":
        from fluxdistributed_tpu.parallel.ep import moe_apply

        if not is_lm:
            raise SystemExit("--spmd ep needs an lm_* model (MoE blocks)")
        ep_mesh, ep = data_x_mesh(
            "expert", "--expert-parallel", args.expert_parallel)
        nex = args.experts if args.experts is not None else ep
        if nex % ep:
            raise SystemExit(f"--experts {nex} must be a multiple of the "
                             f"expert axis size {ep}")
        moe_kwargs = {
            "moe_every": args.moe_every if args.moe_every is not None else 2,
            "num_experts": nex,
            "moe_fn": moe_apply(
                models.moe_expert_fn, ep_mesh, capacity_factor=2.0,
                batch_axis="data",
            ),
        }

    if is_lm:
        # LM protocol: vocab-sized model, next-token loss, no top-k image
        # metrics; cycles must be explicit (the text stream is unbounded).
        # Pipeline modes build their own per-microbatch loss — passing a
        # loss_fn there is an error by design (trainer raises).
        model = model_fn(vocab=args.vocab, **moe_kwargs, **sp_kwargs,
                         **attn_kwargs)
        if args.spmd in ("pp", "pp_1f1b"):
            lm_extra = {"topk": ()}
        else:
            lm_extra = {"loss_fn": models.lm_loss_fn(model), "topk": ()}
        if args.cycles is None and not hasattr(dataset, "__len__"):
            raise SystemExit("--cycles is required for unbounded token "
                             "streams (synthetic-text has no epoch length; "
                             "text: datasets derive cycles from --epochs)")
    else:
        model = model_fn(num_classes=args.num_classes or dataset.nclasses)
        lm_extra = {}

    lr = args.lr
    if args.total_steps:
        lr = optim.warmup_cosine(args.lr, args.warmup_steps, args.total_steps)
    opt_factory = getattr(optim, args.opt)
    opt = opt_factory(lr)

    if args.pipe is not None and args.spmd not in ("pp", "pp_1f1b"):
        raise SystemExit("--pipe only applies with --spmd pp or pp_1f1b")
    if args.microbatches is not None and args.spmd not in ("pp", "pp_1f1b"):
        raise SystemExit("--microbatches only applies with --spmd pp or pp_1f1b")
    if args.pp_interleave and args.spmd != "pp_1f1b":
        raise SystemExit("--pp-interleave only applies with --spmd pp_1f1b")
    if args.pp_schedule != "1f1b" and args.spmd != "pp_1f1b":
        raise SystemExit("--pp-schedule zb only applies with --spmd pp_1f1b")
    if args.pp_plan is not None and args.spmd not in ("pp", "pp_1f1b"):
        raise SystemExit("--pp-plan only applies with --spmd pp or pp_1f1b")
    if args.pp_plan is not None and args.pp_interleave:
        raise SystemExit("--pp-plan cannot combine with --pp-interleave "
                         "(planner boundaries are contiguous block ranges)")
    if (args.expert_parallel is not None or args.experts is not None
            or args.moe_every is not None) and args.spmd != "ep":
        raise SystemExit(
            "--expert-parallel/--experts/--moe-every only apply with --spmd ep")
    if args.seq_parallel is not None and args.spmd != "sp":
        raise SystemExit("--seq-parallel only applies with --spmd sp")
    if args.zero1 and args.spmd not in ("jit", "dp", "shard_map"):
        raise SystemExit("--zero1 only applies with --spmd jit/dp/shard_map")
    if args.layout is not None:
        if args.spmd not in ("jit", "dp"):
            raise SystemExit("--layout builds the rule-derived 3-D step "
                             "and needs --spmd jit (the default)")
        if args.zero1:
            raise SystemExit("--layout cannot combine with --zero1 (a "
                             "layout's fsdp axis already shards the "
                             "optimizer state)")
    if (args.hbm_bytes is not None or args.layout_report) \
            and args.layout != "auto":
        raise SystemExit("--hbm-bytes/--layout-report only apply with "
                         "--layout auto")
    if args.sp_strategy != "ring" and args.spmd != "sp":
        raise SystemExit("--sp-strategy only applies with --spmd sp")
    if args.spmd in ("pp", "pp_1f1b"):
        mesh, _ = data_x_mesh("pipe", "--pipe", args.pipe)
        lm_extra["num_microbatches"] = args.microbatches
        lm_extra["pipeline_interleave"] = args.pp_interleave
        lm_extra["pipeline_schedule"] = args.pp_schedule
        if args.pp_plan:
            plan = _resolve_pp_plan(args, model, mesh)
            lm_extra["pp_plan"] = plan
            if multihost.is_coordinator():
                print(plan.describe())
    elif args.spmd == "ep":
        mesh = ep_mesh
    elif args.spmd == "sp":
        mesh = sp_mesh
    elif args.layout is not None:
        # declarative dp x fsdp x tp layout (rule-derived sharding);
        # 'auto' = the picker: price every candidate's real compiled
        # step, rank by headroom, tiebreak by the collective ledger
        import numpy as np

        from fluxdistributed_tpu.parallel import layout as layout_lib

        if args.layout == "auto":
            from fluxdistributed_tpu.data.loader import batch_to_dict

            draw = dataset.batch(np.random.default_rng(0), args.batch_size)
            bd = batch_to_dict(draw, getattr(dataset, "nclasses", None))
            batch_struct = {
                k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
                for k, v in bd.items()}
            try:
                pick_report = layout_lib.pick(
                    model, batch_struct, opt, hbm_bytes=args.hbm_bytes,
                    loss_fn=lm_extra.get("loss_fn"))
            except layout_lib.LayoutError as e:
                rep = getattr(e, "report", None)
                if rep is not None:
                    if multihost.is_coordinator():
                        print(rep.describe())
                    if args.layout_report:
                        rep.save(args.layout_report)
                raise SystemExit(f"--layout auto: {e}")
            chosen = pick_report.chosen
            if multihost.is_coordinator():
                print(pick_report.describe())
            if args.layout_report:
                pick_report.save(args.layout_report)
        mesh = chosen.build_mesh()
        lm_extra["layout"] = chosen
    else:
        mesh = fd.data_mesh()
    if multihost.is_coordinator():
        print(
            f"devices: {jax.device_count()} ({jax.local_device_count()}/host x "
            f"{jax.process_count()} hosts), platform "
            f"{jax.devices()[0].platform}, mesh {dict(mesh.shape)}"
        )

    # the compiled grad sentinel rides dp.make_train_step; other modes
    # still get the guard POLICY loss-only (non-finite loss + spikes),
    # so --guard degrades instead of erroring there
    guard_sentinel = args.guard and args.spmd in ("jit", "dp", "sp",
                                                  "ep", "pp")
    if args.guard and not guard_sentinel and multihost.is_coordinator():
        print(f"guard: spmd={args.spmd} has no compiled grad sentinel — "
              "running loss-only (non-finite loss + spike detection; "
              "gradient blow-ups that keep the loss finite pass unseen)")

    task = prepare_training(
        model, dataset, opt,
        mesh=mesh,
        batch_size=args.batch_size,
        epochs=args.epochs,
        cycles=args.cycles,
        val_dataset=val_dataset,
        spmd=args.spmd,
        zero1=args.zero1,
        steps_per_call=args.steps_per_call,
        cache_dir=compilation.resolve_cache_dir(args.compile_cache),
        aot=args.aot,
        warmup=args.prewarm,
        strict_checks=args.strict_checks,
        guard=guard_sentinel,
        **lm_extra,
    )

    if args.resume and args.checkpoint_dir:
        from fluxdistributed_tpu.train import resume_training

        manifest = resume_training(task, args.checkpoint_dir)
        if multihost.is_coordinator() and (
                manifest is not None or int(task.state.step)):
            src = ("RESUME manifest" if manifest is not None
                   else "latest checkpoint (no manifest)")
            print(f"resumed from step {int(task.state.step)} at item "
                  f"{getattr(task.loader, 'start', 0)} via {src}")

    if args.replay_step is not None:
        import json as json_lib

        from fluxdistributed_tpu.train import replay_item

        # one quarantined step, re-executed from checkpoint + cursor
        # for diagnosis — never trains, never mutates the state
        report = replay_item(task, args.replay_step)
        print(json_lib.dumps(report))
        return 0

    if args.wandb:
        from fluxdistributed_tpu.train.logging import WandbLogger

        # push the full run configuration at init (reference
        # src/loggers/wandb.jl:1 passes config= to WandbLogger): every
        # arch/spmd/optimizer flag plus the resolved runtime facts —
        # runs become comparable by WHAT they trained, not just curves
        run_config = dict(sorted(vars(args).items()))
        run_config.update(
            devices=jax.device_count(),
            hosts=jax.process_count(),
            platform=jax.devices()[0].platform,
            mesh={k: int(v) for k, v in dict(mesh.shape).items()},
        )
        logger = WandbLogger(project="fluxdistributed_tpu", config=run_config)
    else:
        # per-host logs like the reference's per-worker @info records;
        # non-coordinators stay quiet unless --verbose
        logger = ConsoleLogger() if (multihost.is_coordinator() or args.verbose) else NullLogger()

    # Unified observability: phase metrics, compile counters and the step
    # timeline always on; watchdog/endpoints/files per flags.  The
    # metrics endpoint binds on the coordinator only (a fake cluster runs
    # many processes per host — N processes racing for one port helps
    # nobody).
    from fluxdistributed_tpu.obs import (
        Observation, StepWatchdog, get_registry,
        start_metrics_server,
    )

    observation = Observation(
        watchdog=(StepWatchdog(factor=args.watchdog_factor,
                               escalate_after=args.watchdog_escalate)
                  if args.watchdog_factor else None),
        trace_path=args.trace_events,
        steady_after=args.steady_after,
        jsonl_path=args.metrics_jsonl,
        profile_path=args.profile_out,
        flight_path=args.flight,
    )
    metrics_srv = None
    if args.metrics_port is not None and multihost.is_coordinator():
        reg = get_registry()

        def _health():
            return {
                "ok": reg.value("fdtpu_watchdog_stalled") < 1,
                "steps": reg.value("fdtpu_train_steps_total"),
                "oom_skipped": reg.value("fdtpu_train_oom_skipped_total"),
                "compiles": reg.value("fdtpu_jax_compiles_total"),
                "steady_recompiles": reg.value(
                    "fdtpu_jax_steady_recompiles_total"),
                "escalations": reg.value(
                    "fdtpu_watchdog_escalations_total"),
                "quarantined": reg.value("fdtpu_guard_quarantine_size"),
            }

        metrics_srv = start_metrics_server(
            port=args.metrics_port, health_fn=_health)
        print(f"metrics: http://0.0.0.0:{metrics_srv.port}/metrics "
              f"(+ /healthz)")

    guard_cfg = None
    if args.guard:
        from fluxdistributed_tpu.train import GuardConfig

        guard_cfg = GuardConfig(
            zmax=args.guard_zmax,
            window=args.guard_window,
            rollback_after=args.guard_rollback_after,
        )

    from fluxdistributed_tpu.train import GuardHalt

    t_train = time.monotonic()

    def _ledger(status, error=None, retryable=None, live=False):
        """Append this run's row to the cross-run ledger.  Best-effort
        on every exit path — the ledger must never change an exit code.
        The topology fingerprint calls ``jax.devices()``, which can
        HANG on a wedged backend, so it is only computed when ``live``
        says the backend provably just answered (done/halt/preempt —
        never on the crash path)."""
        if not args.runs_ledger:
            return
        try:
            from fluxdistributed_tpu.compilation import (
                topology_fingerprint,
            )
            from fluxdistributed_tpu.obs import get_registry
            from fluxdistributed_tpu.obs import runs as runs_lib

            reg = get_registry()
            fp = None
            if live:
                try:
                    fp = topology_fingerprint(mesh)
                except Exception:  # noqa: BLE001
                    fp = None
            wall = max(time.monotonic() - t_train, 1e-9)
            steps = reg.value("fdtpu_train_steps_total")
            runs_lib.append_run(args.runs_ledger, runs_lib.run_record(
                "train",
                fingerprint=fp,
                phase="train",
                retryable=retryable,
                error=error,
                metrics={
                    "steps": steps,
                    "steps_per_sec": steps / wall,
                    "wall_seconds": wall,
                    "compile_seconds": reg.value(
                        "fdtpu_jax_compile_seconds_total"),
                    "oom_skipped": reg.value(
                        "fdtpu_train_oom_skipped_total"),
                },
                flight=args.flight,
                status=status,
            ))
        except Exception as e:  # noqa: BLE001
            print(f"runs ledger append failed: {type(e).__name__}: {e}",
                  file=sys.stderr)

    try:
        train(
            task,
            print_every=args.print_every,
            eval_every=args.eval_every,
            topk=() if is_lm else (1, 5, 10),
            logger=logger,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=args.checkpoint_every,
            verbose=args.verbose,
            observation=observation,
            handle_signals=True,
            guard=guard_cfg,
        )
    except GuardHalt as e:
        # recovery is looping: a DISTINCT, deliberately NON-retryable
        # exit code — a supervisor must page a human, not requeue
        _ledger("halted", error=str(e), retryable=False, live=True)
        if multihost.is_coordinator():
            print(f"guard halt: {e} (exit code {faults.HALTED_RC}, "
                  "retryable: false)")
        return faults.HALTED_RC
    except faults.Preempted as e:
        # checkpoint + RESUME manifest are already durably on disk;
        # the DISTINCT exit code tells a supervisor "requeue me with
        # --resume", unlike 0 (done) or 1 (crashed)
        _ledger("preempted", error=str(e), retryable=True, live=True)
        if multihost.is_coordinator():
            print(f"preempted: {e} — resume with --resume "
                  f"--checkpoint-dir {args.checkpoint_dir} "
                  f"(exit code {faults.PREEMPTED_RC})")
        return faults.PREEMPTED_RC
    except BaseException as e:
        # a crash record with NO fingerprint (the backend may be the
        # thing that died — fingerprinting it could hang the exit)
        _ledger("crashed", error=f"{type(e).__name__}: {e}",
                retryable=None, live=False)
        raise
    finally:
        if metrics_srv is not None:
            metrics_srv.stop()
    _ledger("done", live=True)
    multihost.sync_global_devices("train_done")
    if args.final_eval:
        from fluxdistributed_tpu.train import evaluate

        metrics = evaluate(
            task, val_dataset, batch_size=args.batch_size,
            topk=() if is_lm else (1, 5, 10),
        )
        if multihost.is_coordinator():
            parts = ", ".join(
                f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
                for k, v in metrics.items()
            )
            print(f"final eval: {parts}")
    if multihost.is_coordinator():
        print(f"done: {int(task.state.step)} steps, {task.num_missed} missed")
    if task.num_missed:
        # OOM-skip keeps a run alive, it does not make it a success: a
        # run that skipped batches (all of them, at worst) must not
        # look green to whatever started it
        print(f"{task.num_missed} batch(es) were skipped on device OOM — "
              "exit code 1 (reduce --batch-size)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

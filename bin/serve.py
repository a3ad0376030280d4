#!/usr/bin/env python
"""Inference server — webcam demo (vision) or LM serving (``--lm``).

The reference's Pluto notebook embeds an HTML/JS webcam widget
(bin/pluto.jl:133-334) and classifies captured frames with a trained
model (:338-382).  The analog here is a tiny stdlib HTTP server:

* ``GET /``        — a self-contained HTML page that opens the webcam
                     (``getUserMedia``), draws frames to a canvas, and
                     POSTs JPEG snapshots to ``/predict``;
* ``POST /predict``— decode → preprocess (the training pipeline's
                     resize-256/center-crop-224/normalize) → one jitted
                     forward pass → JSON top-k labels.

    python bin/serve.py --model resnet50 --torch-weights r50.pt \
        --synset LOC_synset_mapping.txt --port 8000

Then open http://localhost:8000 in a browser.  Works with trainer
checkpoints (``--checkpoint``), torchvision-layout weights
(``--torch-weights``), or random init (demo mode).  Remote weights
(``http(s)://`` / ``gs://``) are fetched through the dataset source
cache.

With ``--lm`` the server instead fronts the continuous-batching LM
engine (``fluxdistributed_tpu.serve``): ``POST /v1/generate`` with
optional chunked streaming plus ``/healthz`` and ``/metrics``:

    python bin/serve.py --lm --model lm_tiny --checkpoint ck/ \
        --max-slots 8 --max-len 1024 --port 8000
    curl -d '{"prompt": "The quick", "max_tokens": 64}' \
        localhost:8000/v1/generate
"""

from __future__ import annotations

import argparse
import io
import json
import sys

HTML = """<!doctype html>
<html><head><title>fluxdistributed_tpu live inference</title><style>
 body{font-family:sans-serif;max-width:720px;margin:2em auto}
 video,canvas{width:320px;height:240px;background:#222;border-radius:8px}
 table{border-collapse:collapse;margin-top:1em}
 td,th{padding:4px 12px;border-bottom:1px solid #ccc;text-align:left}
</style></head><body>
<h2>Live inference</h2>
<p>Frames are captured from your camera and classified server-side.</p>
<video id="v" autoplay playsinline muted></video>
<canvas id="c" width="320" height="240" style="display:none"></canvas>
<p><button id="go">start</button> <span id="status"></span></p>
<table id="preds"><thead><tr><th>#</th><th>class</th><th>p</th></tr></thead>
<tbody></tbody></table>
<script>
const v=document.getElementById('v'),c=document.getElementById('c'),
      ctx=c.getContext('2d'),tb=document.querySelector('#preds tbody'),
      st=document.getElementById('status');
let running=false;
async function tick(){
  if(!running) return;
  ctx.drawImage(v,0,0,c.width,c.height);
  const blob=await new Promise(r=>c.toBlob(r,'image/jpeg',0.8));
  try{
    const resp=await fetch('/predict',{method:'POST',body:blob});
    const data=await resp.json();
    tb.innerHTML=data.predictions.map((p,i)=>
      `<tr><td>${i+1}</td><td>${p.label}</td><td>${p.prob.toFixed(3)}</td></tr>`).join('');
    st.textContent=`${data.ms.toFixed(0)} ms/frame`;
  }catch(e){st.textContent=e; running=false;}
  setTimeout(tick,250);
}
document.getElementById('go').onclick=async()=>{
  if(running){running=false;return;}
  const s=await navigator.mediaDevices.getUserMedia({video:true});
  v.srcObject=s; running=true; tick();
};
</script></body></html>"""


def build_parser():
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--model", default="resnet50")
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--checkpoint", default=None,
                   help="trainer checkpoint dir (http(s)://- or gs://-"
                        "fetched; remote .zip dirs are unpacked)")
    p.add_argument("--torch-weights", default=None)
    p.add_argument("--synset", default=None)
    p.add_argument("--topk", type=int, default=3)
    p.add_argument("--port", type=int, default=8000,
                   help="0 binds an ephemeral port; LM mode announces "
                        "the bound port as an FDTPU_SERVE_PORT=<n> "
                        "stdout line (and on /healthz) so a router or "
                        "test can orchestrate a fleet race-free")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--platform", default=None)
    # --- LM serving mode (continuous-batching engine) ---
    p.add_argument("--lm", action="store_true",
                   help="serve a TransformerLM through the continuous-"
                        "batching engine (POST /v1/generate) instead of "
                        "the vision webcam demo")
    p.add_argument("--vocab", type=int, default=256,
                   help="LM vocab size (256 = byte-level text prompts)")
    p.add_argument("--step", type=int, default=None,
                   help="specific checkpoint step (LM mode)")
    p.add_argument("--max-slots", type=int, default=8,
                   help="concurrent decode slots (the fixed compiled "
                        "batch of the decode step)")
    p.add_argument("--max-len", type=int, default=1024,
                   help="per-slot KV budget: prompt + generated tokens")
    p.add_argument("--buckets", default="128,512,2048",
                   help="comma-separated prefill shape buckets (prompts "
                        "pad up to the smallest covering bucket)")
    p.add_argument("--max-queue", type=int, default=64,
                   help="admission queue bound; beyond it /v1/generate "
                        "returns 429 (backpressure)")
    # paged KV cache (serve.cache_layout): HBM scales with live tokens
    p.add_argument("--paged", action="store_true",
                   help="paged KV cache layout: a shared pool of fixed-"
                        "size blocks with per-slot page tables instead "
                        "of worst-case rows per slot; freed blocks "
                        "return to the pool on EOS (LM mode)")
    p.add_argument("--kv-block-size", type=int, default=16,
                   help="rows per KV block (--paged)")
    p.add_argument("--kv-blocks", type=int, default=None,
                   help="blocks per layer in the pool (--paged); default "
                        "sizes for full capacity — set it SMALLER to "
                        "make HBM scale with live tokens and let "
                        "admission backpressure cover the tail")
    p.add_argument("--prefill-chunk", type=int, default=None,
                   help="prompt positions per prefill chunk; chunks "
                        "interleave with decode ticks so a long prompt "
                        "cannot spike TTFT for resident requests "
                        "(--paged defaults to 128; also valid on the "
                        "dense layout)")
    p.add_argument("--prefix-cache", action="store_true",
                   help="hash + refcount completed prompt blocks so "
                        "shared system prompts prefill once "
                        "(needs --paged, plain attention)")
    p.add_argument("--attention-impl", default="xla",
                   choices=["xla", "pallas"],
                   help="decode attention core: 'pallas' runs the "
                        "flash-decode kernel suite (ops/pallas_decode) — "
                        "cursor block-skip, native windowed-ring/paged "
                        "walks — with an XLA fallback off TPU; 'xla' is "
                        "the reference gather+mask path")
    p.add_argument("--kv-dtype", default=None,
                   choices=["int8", "fp8"],
                   help="quantize KV-cache storage (per-row scales ride "
                        "in the cache; dequant is fused into reads): "
                        "int8 halves bf16 KV bytes, quarters f32")
    p.add_argument("--kv-heads", type=int, default=None,
                   help="match the trainer's --kv-heads (GQA)")
    p.add_argument("--window", type=int, default=None,
                   help="match the trainer's --window (ring KV cache)")
    p.add_argument("--sinks", type=int, default=0,
                   help="match the trainer's --sinks (attention sinks)")
    p.add_argument("--norm", default="layernorm",
                   choices=["layernorm", "rmsnorm"],
                   help="match the trainer's --norm")
    p.add_argument("--mlp", default="gelu", choices=["gelu", "swiglu"],
                   help="match the trainer's --mlp")
    p.add_argument("--trace-requests", default=None, metavar="PATH",
                   help="record request-scoped lifecycle events "
                        "(enqueue/queue-wait/prefill chunks/first "
                        "token/decode ticks/finish) in a bounded ring "
                        "and write a Perfetto trace with one track per "
                        "request here at shutdown; the live ring is "
                        "also served at GET /trace (LM mode)")
    # cold-start controls (fluxdistributed_tpu.compilation)
    p.add_argument("--drain-timeout", type=float, default=30.0,
                   help="graceful-drain bound for --lm: on SIGTERM the "
                        "server stops admissions (503), finishes "
                        "in-flight decodes for up to this many seconds "
                        "(healthz reports draining), then exits 0 — "
                        "kube-style rolling restarts lose no tokens")
    p.add_argument("--prewarm", action="store_true",
                   help="pre-compile every prefill bucket, the splice "
                        "and the all-slot decode step BEFORE binding the "
                        "port — the first request pays decode latency, "
                        "not the engine's whole compile pool (LM mode)")
    p.add_argument("--compile-cache", default=None, metavar="DIR",
                   help="directory of JAX's persistent compilation "
                        "cache (always on: a restarted server reads its "
                        "XLA compiles from disk instead of redoing "
                        "them).  JAX_COMPILATION_CACHE_DIR wins when "
                        "set; default: .jax_cache/ in the checkout")
    p.add_argument("--aot-dir", default=None, metavar="DIR",
                   help="serialized-executable pool for the engine's "
                        "programs: load from disk when topology+model "
                        "match, else compile now and serialize for the "
                        "next process (skips tracing AND compiling on "
                        "restart; LM mode)")
    p.add_argument("--fault-plan", default=None, metavar="JSON",
                   help="install a deterministic fault-injection plan "
                        "(fluxdistributed_tpu.faults) before serving — "
                        "JSON object or @path/to/plan.json, e.g. "
                        "'{\"fail\": [{\"site\": \"serve.tick\", "
                        "\"at\": 40, \"action\": \"exit\"}]}' is a "
                        "replica crash at scheduler tick 40 (the "
                        "router failover test harness)")
    p.add_argument("--fake-engine", action="store_true",
                   help="serve a deterministic pure-python engine "
                        "(serve.testing.FakeLMEngine) instead of a real "
                        "model — no compiles, instant startup; the "
                        "router fleet test/dev scaffold (LM mode)")
    p.add_argument("--fake-step-delay", type=float, default=0.002,
                   help="seconds each fake-engine decode tick sleeps "
                        "(gives drains and kills measurable width)")
    return p


def make_lm_app(args):
    """Build the LM-serving stack: ``(LMServer, Scheduler)``.

    Separate from HTTP wiring so tests can drive the scheduler directly
    (the ``make_app`` pattern below).
    """
    if args.fake_engine:
        # no model, no compiles: the router fleet scaffold — the HTTP/
        # scheduler surface is real, only the tokens are fake
        from fluxdistributed_tpu.serve.testing import FakeLMEngine

        engine = FakeLMEngine(max_slots=args.max_slots,
                              max_len=args.max_len,
                              step_delay=args.fake_step_delay,
                              vocab=args.vocab)
        return _wire_lm_stack(args, engine)

    import time

    import jax
    import numpy as np

    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    from fluxdistributed_tpu import compilation, models
    from fluxdistributed_tpu.serve import LMEngine

    compilation.enable_persistent_cache(args.compile_cache)

    model_fn = getattr(models, args.model, None)
    if model_fn is None or not args.model.startswith("lm_"):
        raise SystemExit(f"--lm needs an lm_* model factory, got {args.model!r}")
    model = model_fn(vocab=args.vocab, num_kv_heads=args.kv_heads,
                     window=args.window, sinks=args.sinks, norm=args.norm,
                     mlp=args.mlp)
    if args.checkpoint:
        from fluxdistributed_tpu.data.sources import fetch_checkpoint
        from fluxdistributed_tpu.train import load_checkpoint

        restored = load_checkpoint(fetch_checkpoint(args.checkpoint),
                                   step=args.step)
        params = restored["params"]
        print(f"loaded checkpoint step "
              f"{int(np.asarray(restored.get('step', -1)))} "
              f"from {args.checkpoint}", file=sys.stderr)
    else:
        params = model.init(
            jax.random.PRNGKey(0), np.zeros((1, 2), np.int32), train=False
        )["params"]
        print("no --checkpoint: serving a RANDOM-INIT model", file=sys.stderr)

    try:
        buckets = tuple(int(b) for b in args.buckets.split(","))
    except ValueError:
        raise SystemExit(f"--buckets must be comma-separated ints, got "
                         f"{args.buckets!r}")
    t0 = time.perf_counter()
    engine = LMEngine(model, params, max_slots=args.max_slots,
                      max_len=args.max_len, buckets=buckets,
                      prewarm=args.prewarm, aot_dir=args.aot_dir,
                      layout="paged" if args.paged else "dense",
                      kv_block_size=args.kv_block_size,
                      kv_blocks=args.kv_blocks,
                      prefill_chunk=args.prefill_chunk,
                      prefix_cache=args.prefix_cache,
                      attention_impl=args.attention_impl,
                      kv_dtype=args.kv_dtype)
    if args.prewarm or args.aot_dir:
        print(f"engine ready in {time.perf_counter() - t0:.1f}s "
              f"(compile_stats={engine.compile_stats()})", file=sys.stderr)
    return _wire_lm_stack(args, engine)


def _wire_lm_stack(args, engine):
    """Scheduler + LMServer over any engine (real or fake) — ONE place
    so the fake-engine fleet cannot diverge from the real serving
    path."""
    from fluxdistributed_tpu.serve import LMServer, Scheduler

    reqtrace = None
    if getattr(args, "trace_requests", None):
        from fluxdistributed_tpu.obs import RequestTracer

        reqtrace = RequestTracer()
    scheduler = Scheduler(engine, max_queue=args.max_queue,
                          reqtrace=reqtrace)
    return LMServer(scheduler, args.vocab), scheduler


def make_app(args):
    """Build the ``predict(jpeg_bytes) -> [{label, prob}]`` closure;
    separate from serving so tests can drive it directly."""
    import jax
    import numpy as np

    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    from fluxdistributed_tpu import models as models_lib
    from fluxdistributed_tpu.data.preprocess import preprocess

    from fluxdistributed_tpu import compilation

    compilation.enable_persistent_cache(args.compile_cache)

    factory = getattr(models_lib, args.model, None)
    if factory is None:
        raise SystemExit(f"unknown model {args.model!r}")
    if args.torch_weights and args.checkpoint:
        raise SystemExit("--torch-weights and --checkpoint are mutually exclusive")
    from fluxdistributed_tpu.data.sources import fetch_artifact, fetch_checkpoint

    dummy = np.zeros((1, 224, 224, 3), np.float32)
    if args.torch_weights:
        from fluxdistributed_tpu.models.torch_import import load_torch_weights_for

        try:
            model, variables = load_torch_weights_for(
                args.model, args.num_classes, fetch_artifact(args.torch_weights)
            )
        except ValueError as e:
            raise SystemExit(str(e))
    elif args.checkpoint:
        model = factory(num_classes=args.num_classes)
        from fluxdistributed_tpu.train.checkpoint import load_checkpoint

        restored = load_checkpoint(fetch_checkpoint(args.checkpoint))
        variables = {"params": restored["params"], **restored.get("model_state", {})}
    else:
        model = factory(num_classes=args.num_classes)
        variables = model.init(jax.random.PRNGKey(0), dummy, train=False)

    names = None
    if args.synset:
        from fluxdistributed_tpu.data.imagenet import labels

        names = [n.split(",")[0] for n in labels(fetch_artifact(args.synset)).names]

    fwd = jax.jit(lambda v, x: model.apply(v, x, train=False))
    fwd(variables, dummy)  # compile before the first request

    def predict(jpeg_bytes: bytes):
        from PIL import Image

        img = Image.open(io.BytesIO(jpeg_bytes)).convert("RGB")
        x = preprocess(np.asarray(img, np.uint8))[None]
        logits = np.asarray(fwd(variables, x))[0]
        p = np.exp(logits - logits.max())
        p /= p.sum()
        top = np.argsort(-p)[: args.topk]
        return [
            {"label": names[i] if names else f"class {i}", "prob": float(p[i])}
            for i in top
        ]

    return predict


def serve(args, predict):
    import http.server
    import time

    class Handler(http.server.BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def _send(self, code, body: bytes, ctype: str):
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path in ("/", "/index.html"):
                self._send(200, HTML.encode(), "text/html")
            else:
                self._send(404, b"not found", "text/plain")

        def do_POST(self):
            if self.path != "/predict":
                self._send(404, b"not found", "text/plain")
                return
            n = int(self.headers.get("Content-Length", 0))
            data = self.rfile.read(n)
            t0 = time.perf_counter()
            try:
                preds = predict(data)
            except Exception as e:  # bad frame: report, don't die
                self._send(400, json.dumps({"error": str(e)}).encode(),
                           "application/json")
                return
            body = json.dumps({
                "predictions": preds,
                "ms": (time.perf_counter() - t0) * 1e3,
            }).encode()
            self._send(200, body, "application/json")

    srv = http.server.ThreadingHTTPServer((args.host, args.port), Handler)
    print(f"serving on http://{args.host}:{srv.server_address[1]}/ (ctrl-c to stop)")
    return srv


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if getattr(args, "fault_plan", None):
        from fluxdistributed_tpu import faults

        spec = args.fault_plan
        if spec.startswith("@"):
            with open(spec[1:]) as f:
                spec = f.read()
        faults.install_plan(faults.FaultPlan.from_spec(json.loads(spec)))
    if args.lm:
        lm_server, scheduler = make_lm_app(args)
        srv = lm_server.serve(args.host, args.port)
        # SIGTERM → stop admissions, finish in-flight decodes (bounded),
        # shut the HTTP server down, exit 0 — the graceful-drain path
        lm_server.install_drain_handler(httpd=srv,
                                        timeout=args.drain_timeout)
        # the machine-readable bound-port announcement (--port 0 gives
        # an ephemeral one): routers and tests read THIS line, humans
        # read the next one
        print(f"FDTPU_SERVE_PORT={srv.server_address[1]}", flush=True)
        print(f"serving LM on http://{args.host}:{srv.server_address[1]}/"
              f"v1/generate (ctrl-c to stop; SIGTERM drains "
              f"<= {args.drain_timeout:.0f}s)", flush=True)
        try:
            srv.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            lm_server.stop_loop()
            if scheduler.reqtrace is not None:
                n = scheduler.reqtrace.export_chrome_trace(
                    args.trace_requests)
                print(f"request trace ({n} events) written to "
                      f"{args.trace_requests}", file=sys.stderr)
        return 0
    predict = make_app(args)
    srv = serve(args, predict)
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())

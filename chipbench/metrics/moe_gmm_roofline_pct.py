"""Kernels: the grouped matrix products' share of their roofline, over
the traced steps: the experts' three projections, which
``parallel/ep.py`` runs through ``ops/pallas_gmm.py`` (``gmm``,
``gmm_t``, ``tgmm``), all under the one name ``fdtpu_gmm`` in a trace.
The least time of a step is the larger of its operations over the
chip's bf16 peak and its bytes over the HBM bandwidth; the share is
that, times the whole steps the traced slice holds, over the seconds
under that name inside them, read from the trace's ``kernels``
however little time it took.  Nothing to read where the program counts no routed slots
(``fdtpu_moe_slots_total``) or the trace holds no such kernel.

What a step needs, each product once: the rows are the token-slots
routed to the experts held here, counted by the program (the mean a
step over the run, all expert layers together).  A layer runs 3
products forward (gate, up, down) and 6 backward (each product's two
gradients): each is ``2 rows D M`` operations.  A rematerialised layer
counts its forward once (ROADMAP S4(b) had the count of two stay; it
goes for the reason ``attn_roofline_pct`` gives: the yardstick is the
algorithm's work, so a program that stops recomputing cannot read over
100).  Bytes are what the algorithm needs, whatever tiles implement it:
a product reads or writes the rows at both widths once in the compute
type; the held experts' weights are read once by each of the 6 products
that take them (3 forward, the rows' 3 gradients) in the compute type,
and their 3 gradients written once in float32.  They do not follow the
tiles (``pallas_gmm.operand_reads``): a yardstick that followed the
implementation would show no gain in its own share for a change that
reads fewer bytes."""

KERNEL = "fdtpu_gmm"


def step_work(config: dict, rows_per_step: float) -> tuple:
    """``(operations, bytes)`` of one step; ``rows_per_step`` over all
    the expert layers."""
    kw = config["model"]["kwargs"]
    d, m = kw["dim"], kw["moe_intermediate_size"]
    held = (kw.get("experts_held") or [0, kw["n_routed_experts"]])[1]
    layers = (kw["num_layers"] - kw.get("first_k_dense_replace", 0)
              + kw.get("num_nextn_predict_layers", 0))
    products = 3 + 6
    ops = products * 2 * rows_per_step * d * m
    row_bytes = products * rows_per_step * (d + m) * 2
    weight_bytes = layers * held * d * m * (6 * 2 + 3 * 4)
    return ops, row_bytes + weight_bytes


def rows_per_step():
    """Held token-slots a step, from the program's counters; None where
    the program has none."""
    try:
        from fluxdistributed_tpu.obs import get_registry
    except ImportError:
        return None
    reg = get_registry()
    balance = reg.get("fdtpu_moe_load_max_over_mean")
    if balance is None or reg.get("fdtpu_moe_slots_total") is None:
        return None
    counts = [c["count"] for c in balance.series().values()]
    if not counts or not max(counts):
        return None
    return reg.value("fdtpu_moe_slots_total", "held") / max(counts)


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"] or "moe_intermediate_size" not in ctx[
            "config"]["model"]["kwargs"]:
        return None
    seconds = t["kernels"].get(KERNEL)
    rows = rows_per_step()
    if not seconds or not rows:
        return None
    ops, nbytes = step_work(ctx["config"], rows)
    least = max(ops / (ctx["peaks"]["bf16_tflops"] * 1e12),
                nbytes / (ctx["peaks"]["hbm_gb_per_s"] * 1e9))
    return 100.0 * least * t["steps"] / seconds

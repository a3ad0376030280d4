"""Memory-observability normalisers.

``Compiled.memory_analysis()`` and ``Device.memory_stats()`` as plain
dicts, None where the backend reports nothing (CPU devices report no
memory stats).  Every consumer (obs.memstats, the HBM gauges,
bin/fit.py) treats None as "unavailable", never an error.
"""

from __future__ import annotations

#: CompiledMemoryStats fields we normalize, in the XLA spelling minus
#: the ``_size_in_bytes`` suffix.  ``peak`` is derived: the XLA
#: approximation of live HBM while the program runs is arguments +
#: outputs + temporaries minus the aliased (donated) overlap.
_MEMORY_FIELDS = ("generated_code", "argument", "output", "alias", "temp")


def compiled_memory_analysis(compiled) -> "dict | None":
    """``Compiled.memory_analysis()`` normalized to plain int bytes:
    ``{"generated_code_bytes", "argument_bytes", "output_bytes",
    "alias_bytes", "temp_bytes", "peak_bytes"}``.

    Returns None — never raises — when this jax build has no
    ``memory_analysis``, the backend reports none (some plugin runtimes
    return None), or the stats object lacks the expected fields.  A
    missing memory model must degrade the observability artifact, not
    kill the run producing it.
    """
    fn = getattr(compiled, "memory_analysis", None)
    if fn is None:
        return None
    try:
        st = fn()
    except Exception:  # noqa: BLE001 — absence/unsupported, not failure
        return None
    if st is None:
        return None
    out = {}
    for name in _MEMORY_FIELDS:
        v = getattr(st, f"{name}_size_in_bytes", None)
        if v is None and isinstance(st, dict):
            v = st.get(f"{name}_size_in_bytes")
        if v is None:
            return None
        out[f"{name}_bytes"] = int(v)
    out["peak_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                         + out["temp_bytes"] - out["alias_bytes"])
    return out


def device_memory_stats(device) -> "dict | None":
    """``Device.memory_stats()`` as a plain dict, or None when the
    device does not report memory (CPU devices return None; older
    plugin backends lack the method).  Never raises."""
    fn = getattr(device, "memory_stats", None)
    if fn is None:
        return None
    try:
        st = fn()
    except Exception:  # noqa: BLE001 — absence/unsupported, not failure
        return None
    if not st:
        return None
    return dict(st)

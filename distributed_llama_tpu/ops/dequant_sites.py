"""Registry of the sanctioned Q40 dequantization sites.

A Q40 weight must live in HBM as packed codes + scales; materializing its
f32 form costs 8x the bytes (and on the XLA fallback path it is the single
largest transient in the program). Exactly a handful of functions are
ALLOWED to do that materialization:

* ``ops/linear.dequantize_weight`` — the XLA dequantize-then-dot fallback
  (and the parity/test path on CPU). On the Pallas serving path the same
  values are produced in-kernel from VMEM tiles and never hit HBM.
* ``ops/pallas_q40`` internals — in-kernel/per-tile dequant helpers.
* ``parallel/tp._wire_gather`` / ``_wire`` and ``ops/linear.fake_quant_q80``
  — the Q80 *buffer* codec on activation vectors (dim-sized, not
  weight-sized; listed so the int8->f32 detector does not misread the wire
  path as a weight dequant).

``analysis/shardcheck.py`` enforces this as contract J005: any large
int->f32 materialization in a traced forward whose call stack touches none
of these sites is a rogue dequant — a weight-sized f32 copy the memory
model does not account for. The registry lives in ops/ (next to the codecs)
so a new sanctioned site lands here, beside its implementation, and the
checker follows automatically. ``tests/test_shardcheck_repo.py`` pins every
entry to a real function so the registry cannot rot.
"""

from __future__ import annotations

# (repo-relative file suffix, function name) pairs. The function name is
# what jax source_info records per traced eqn; the file suffix disambiguates
# same-named helpers across modules.
ALLOWED_DEQUANT_SITES: tuple[tuple[str, str], ...] = (
    ("ops/linear.py", "dequantize_weight"),
    ("ops/linear.py", "fake_quant_q80"),
    ("ops/pallas_q40.py", "_dequant_i4"),
    ("ops/pallas_q40.py", "_dequant_nb"),
    ("parallel/tp.py", "_wire_gather"),
    ("parallel/tp.py", "_wire"),
)


def frame_allowed(file_name: str, function_name: str) -> bool:
    """Is one (file, function) stack frame a registered dequant site?"""
    for suffix, fn in ALLOWED_DEQUANT_SITES:
        if function_name == fn and file_name.replace("\\", "/").endswith(
                suffix):
            return True
    return False


def frames_allowed(frames) -> bool:
    """True when ANY frame of an eqn's user stack is a registered site.

    ``frames`` yields objects with ``file_name``/``function_name`` (the
    jax source_info user-frame surface).
    """
    return any(frame_allowed(f.file_name, f.function_name) for f in frames)

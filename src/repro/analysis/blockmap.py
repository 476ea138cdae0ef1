"""Pass 2 — Pallas block/index-map analyzer.

Symbolically checks every ``select_block_shapes`` outcome (and any
pinned override) over a representative shape sweep for the three
Pallas kernels (``ternary_matmul`` float, ``ternary_matmul_int8``,
``cim_mac``), against the invariants the kernels' correctness rests
on:

  * BM001 — tile alignment: positive blocks, ``bm`` a sublane
    multiple for the arithmetic domain (f32: 8, int8: 32), ``bn``/
    ``bk`` lane multiples (128 — which also keeps the trit2 packed
    tile ``bk/4`` whole), and ``bk`` a ``ROWS_PER_GROUP`` (16)
    multiple for the cim kernel;
  * BM002 — exact grid coverage: the padded iteration space is
    covered by grid x block with zero residue and less than one
    block of overhang per axis;
  * BM003 — index maps in bounds: every BlockSpec index map, at every
    corner of the grid, lands its block inside the padded operand;
  * BM004 — the double-buffered VMEM working set fits the budget the
    selector promises (unless already at the ``bk`` floor);
  * BM005 — masking identities: the padded regions provably
    contribute zero — the w pad byte decodes to exactly 0 in both
    packing modes and both arithmetic domains, x pads with zeros,
    and the cim ADC clip window contains 0 so zero-padded K groups
    pass through unclipped;
  * BM006 — dtype consistency: the kernel abstract-evaluates (under
    ``jax.eval_shape``, no execution) to the contracted output dtype
    for the domain (f32 epilogue for ternary, int32 for the raw cim
    MAC).

``pin_blocks`` injects a block choice over the whole sweep (the
violation-seeding hook the CLI exposes as ``--pin-blocks``).
"""
from __future__ import annotations

import itertools
from typing import Optional

from .base import Finding

PASS = "blockmap"

# (M, K, N) sweep: decode-skinny M, ragged every-axis shapes, exact
# tile multiples, prefill-sized M, deep-K decode shapes
SHAPE_SWEEP = (
    (1, 13, 50),
    (1, 64, 128),
    (4, 4096, 1),
    (7, 96, 333),
    (8, 256, 1000),
    (16, 1024, 128),
    (100, 4096, 16),
    (128, 512, 256),
    (333, 77, 129),
    (256, 4096, 1024),
)

# shapes small enough to also push through jax.eval_shape per cell
EVAL_SHAPES = ((1, 13, 50), (7, 96, 333), (128, 512, 256))


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _cell(kernel, mode, domain, shape, blocks):
    m, k, n = shape
    return (f"{kernel} mode={mode} domain={domain} shape=({m},{k},{n}) "
            f"blocks={tuple(blocks)}")


def _check_alignment(cell, bm, bn, bk, mode, domain, *, cim=False) -> list:
    from repro.kernels.cim_mac import ROWS_PER_GROUP
    from repro.kernels.ternary_matmul import (INT8_SUBLANE, MXU_LANE,
                                              SUBLANE, TRIT2_PER_BYTE)
    out = []
    if min(bm, bn, bk) < 1:
        return [Finding(PASS, "BM001", cell, "non-positive block shape")]
    sublane = INT8_SUBLANE if domain == "int8" else SUBLANE
    if bm % sublane:
        out.append(Finding(PASS, "BM001", cell,
                           f"bm={bm} is not a multiple of the {domain} "
                           f"sublane quantum {sublane}"))
    if bn % MXU_LANE:
        out.append(Finding(PASS, "BM001", cell,
                           f"bn={bn} is not lane-aligned ({MXU_LANE})"))
    if bk % MXU_LANE:
        out.append(Finding(PASS, "BM001", cell,
                           f"bk={bk} is not lane-aligned ({MXU_LANE})"))
    if mode == "trit2" and bk % TRIT2_PER_BYTE:
        out.append(Finding(PASS, "BM001", cell,
                           f"bk={bk} splits the trit2 packed byte "
                           f"({TRIT2_PER_BYTE} trits/byte)"))
    if cim and bk % ROWS_PER_GROUP:
        out.append(Finding(PASS, "BM001", cell,
                           f"bk={bk} splits the cim ADC row group "
                           f"({ROWS_PER_GROUP} rows)"))
    return out


def _check_coverage_and_maps(cell, m, kdim, n, mode, bm, bn, bk) -> list:
    """Recompute the kernels' pad rule from first principles, then
    drive every BlockSpec index map over the grid corners and check
    each block lands inside the padded operand."""
    from repro.kernels.ternary_matmul import TRIT2_PER_BYTE
    out = []
    mp = _round_up(m, bm)
    np_ = _round_up(n, bn)
    kp = _round_up(kdim, bk)
    grid = (mp // bm, np_ // bn, kp // bk)
    # exact coverage: zero residue, less than one block of overhang
    for name, padded, extent, blk, cells in (
            ("M", mp, m, bm, grid[0]), ("N", np_, n, bn, grid[1]),
            ("K", kp, kdim, bk, grid[2])):
        if padded % blk or cells * blk != padded:
            out.append(Finding(PASS, "BM002", cell,
                               f"grid does not tile the padded {name} "
                               f"axis exactly: {cells} x {blk} != "
                               f"{padded}"))
        if padded - extent >= blk:
            out.append(Finding(PASS, "BM002", cell,
                               f"{name} axis pads {padded - extent} >= "
                               f"one full block ({blk}): wasted grid "
                               f"cells"))
    bkw = bk // TRIT2_PER_BYTE if mode == "trit2" else bk
    kwp = kp // TRIT2_PER_BYTE if mode == "trit2" else kp
    # (block_shape, index_map, padded operand extents) per BlockSpec,
    # mirroring the pallas_call in kernels/ternary_matmul.py
    specs = (
        ("x", (bm, bk), lambda i, j, k: (i, k), (mp, kp)),
        ("w", (bkw, bn), lambda i, j, k: (k, j), (kwp, np_)),
        ("scale", (1, bn), lambda i, j, k: (0, j), (1, np_)),
        ("out", (bm, bn), lambda i, j, k: (i, j), (mp, np_)),
    )
    corners = itertools.product(*((0, g - 1) for g in grid))
    for gi, gj, gk in corners:
        for name, blk, index_map, extents in specs:
            idx = index_map(gi, gj, gk)
            for axis, (bidx, bsz, ext) in enumerate(zip(idx, blk,
                                                        extents)):
                if bidx < 0 or (bidx + 1) * bsz > ext:
                    out.append(Finding(
                        PASS, "BM003", cell,
                        f"{name} index map at grid ({gi},{gj},{gk}) "
                        f"puts block {bidx} (size {bsz}) outside the "
                        f"padded axis-{axis} extent {ext}"))
    return out


def _check_vmem(cell, bm, bn, bk, mode, domain) -> list:
    from repro.kernels.ternary_matmul import (MXU_LANE,
                                              VMEM_BUDGET_BYTES,
                                              _vmem_working_set)
    used = _vmem_working_set(bm, bn, bk, mode, domain)
    if used > VMEM_BUDGET_BYTES and bk > MXU_LANE:
        return [Finding(PASS, "BM004", cell,
                        f"working set {used} B exceeds the "
                        f"{VMEM_BUDGET_BYTES} B budget with bk={bk} "
                        f"still above the {MXU_LANE} floor")]
    return []


def _check_masking(cell, mode, domain) -> list:
    """Prove the pad regions contribute zero: run the kernel's own
    decode on a tile of the pad byte (tiny concrete arrays — decode
    only, never a matmul)."""
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels.ternary_matmul import (BASE3_OFFSET,
                                              TRIT2_PER_BYTE, _decode_w)
    out = []
    pad_val = BASE3_OFFSET if mode == "base3" else 0
    tile = jnp.full((TRIT2_PER_BYTE, 8), pad_val, jnp.uint8)
    dtype = jnp.int8 if domain == "int8" else jnp.float32
    dec = np.asarray(_decode_w(tile, mode, dtype))
    if dec.any():
        out.append(Finding(PASS, "BM005", cell,
                           f"pad byte {pad_val} decodes to nonzero "
                           f"values in {dtype}: padded K rows would "
                           f"contribute to the dot"))
    return out


def _check_pad_rule(cell, mode) -> list:
    """Drive ``_pad_to_blocks`` on a tiny ragged operand and verify the
    padded regions hold exactly the zero-decoding constants."""
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels.ternary_matmul import (BASE3_OFFSET,
                                              TRIT2_PER_BYTE,
                                              _pad_to_blocks)
    out = []
    m, kdim, n = 3, 8, 5
    kw = kdim // TRIT2_PER_BYTE if mode == "trit2" else kdim
    x = jnp.ones((m, kdim), jnp.float32)
    w = jnp.full((kw, n), 7, jnp.uint8)
    scale = jnp.ones((n,), jnp.float32)
    xp, wp, sp, mp = _pad_to_blocks(x, w, scale, mode, 8, 8, 16)
    pad_val = BASE3_OFFSET if mode == "base3" else 0
    if np.asarray(xp)[:, kdim:].any() or np.asarray(xp)[m:, :].any():
        out.append(Finding(PASS, "BM005", cell,
                           "x pad region is not zero"))
    wnp = np.asarray(wp)
    if (wnp[kw:, :] != pad_val).any() or (wnp[:, n:] != pad_val).any():
        out.append(Finding(PASS, "BM005", cell,
                           f"w pad region is not the zero-decoding "
                           f"byte {pad_val}"))
    if np.asarray(sp)[n:].any():
        out.append(Finding(PASS, "BM005", cell,
                           "scale pad region is not zero"))
    return out


def _check_cim_clip_window(cell, adc_bits: int = 5) -> list:
    from repro.kernels.cim_mac import ROWS_PER_GROUP
    lo = ROWS_PER_GROUP - 2 ** adc_bits + 1
    hi = ROWS_PER_GROUP
    if not (lo <= 0 <= hi):
        return [Finding(PASS, "BM005", cell,
                        f"ADC clip window [{lo}, {hi}] excludes 0: "
                        f"zero-padded K groups would saturate")]
    return []


def _check_abstract_eval(cell, m, k, n, mode, domain, bm, bn, bk) -> list:
    """Abstract-eval the real kernel with these blocks (pallas
    validates BlockSpec consistency at trace time; nothing runs)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.ternary_matmul import (TRIT2_PER_BYTE,
                                              ternary_matmul,
                                              ternary_matmul_int8)
    kdim = _round_up(k, TRIT2_PER_BYTE) if mode == "trit2" else k
    kw = kdim // TRIT2_PER_BYTE if mode == "trit2" else kdim
    x_dt = jnp.int8 if domain == "int8" else jnp.float32
    x = jax.ShapeDtypeStruct((m, kdim), x_dt)
    w = jax.ShapeDtypeStruct((kw, n), jnp.uint8)
    scale = jax.ShapeDtypeStruct((n,), jnp.float32)
    try:
        if domain == "int8":
            xs = jax.ShapeDtypeStruct((m,), jnp.float32)
            out = jax.eval_shape(
                lambda a, b, c, d: ternary_matmul_int8(
                    a, b, c, d, mode=mode, bm=bm, bn=bn, bk=bk,
                    interpret=True), x, xs, w, scale)
        else:
            out = jax.eval_shape(
                lambda a, b, c: ternary_matmul(
                    a, b, c, mode=mode, bm=bm, bn=bn, bk=bk,
                    interpret=True), x, w, scale)
    except Exception as e:
        return [Finding(PASS, "BM006", cell,
                        f"kernel failed abstract eval with these "
                        f"blocks: {e!r}")]
    if tuple(out.shape) != (m, n) or out.dtype != jnp.float32:
        return [Finding(PASS, "BM006", cell,
                        f"kernel abstract-evals to {out.shape} "
                        f"{out.dtype}, expected ({m}, {n}) float32")]
    return []


def _check_cim_abstract_eval(cell, m, k, n, bm, bn, bk) -> list:
    import jax
    import jax.numpy as jnp
    from repro.kernels.cim_mac import cim_mac
    x = jax.ShapeDtypeStruct((5, m, k), jnp.int8)
    w = jax.ShapeDtypeStruct((5, k, n), jnp.int8)
    try:
        out = jax.eval_shape(
            lambda a, b: cim_mac(a, b, adc_bits=5, bm=bm, bn=bn, bk=bk,
                                 interpret=True), x, w)
    except Exception as e:
        return [Finding(PASS, "BM006", cell,
                        f"cim_mac failed abstract eval: {e!r}")]
    if tuple(out.shape) != (m, n) or out.dtype != jnp.int32:
        return [Finding(PASS, "BM006", cell,
                        f"cim_mac abstract-evals to {out.shape} "
                        f"{out.dtype}, expected ({m}, {n}) int32")]
    return []


def check_ternary_cell(m: int, k: int, n: int, mode: str, domain: str,
                       blocks: Optional[tuple] = None) -> list:
    """All invariants for one ternary-kernel cell; ``blocks`` pins the
    tile choice (violation injection), default = the live selector."""
    from repro.kernels.ternary_matmul import (TRIT2_PER_BYTE,
                                              select_block_shapes)
    kdim = _round_up(k, TRIT2_PER_BYTE) if mode == "trit2" else k
    if blocks is None:
        blocks = select_block_shapes(m, kdim, n, mode, domain=domain)
    bm, bn, bk = blocks
    kernel = "ternary_matmul_int8" if domain == "int8" else \
        "ternary_matmul"
    cell = _cell(kernel, mode, domain, (m, k, n), blocks)
    findings = _check_alignment(cell, bm, bn, bk, mode, domain)
    if any(f.rule == "BM001" and "non-positive" in f.message
           for f in findings):
        return findings           # everything downstream divides by these
    findings += _check_coverage_and_maps(cell, m, kdim, n, mode,
                                         bm, bn, bk)
    findings += _check_vmem(cell, bm, bn, bk, mode, domain)
    findings += _check_masking(cell, mode, domain)
    findings += _check_pad_rule(cell, mode)
    if not findings and (m, k, n) in EVAL_SHAPES:
        findings += _check_abstract_eval(cell, m, k, n, mode, domain,
                                         bm, bn, bk)
    return findings


def check_cim_cell(m: int, k: int, n: int,
                   blocks: Optional[tuple] = None) -> list:
    from repro.kernels.plan import CIM_DEFAULT_BLOCKS
    if blocks is None:
        blocks = CIM_DEFAULT_BLOCKS
    bm, bn, bk = blocks
    cell = _cell("cim_mac", "planes", "int32", (m, k, n), blocks)
    findings = _check_alignment(cell, bm, bn, bk, "base3", "float",
                                cim=True)
    if any(f.rule == "BM001" and "non-positive" in f.message
           for f in findings):
        return findings
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    grid = (mp // bm, np_ // bn, kp // bk)
    specs = (
        ("x", (None, bm, bk), lambda i, j, k: (0, i, k), (1, mp, kp)),
        ("w", (None, bk, bn), lambda i, j, k: (0, k, j), (1, kp, np_)),
        ("out", (bm, bn), lambda i, j, k: (i, j), (mp, np_)),
    )
    for gi, gj, gk in itertools.product(*((0, g - 1) for g in grid)):
        for name, blk, index_map, extents in specs:
            idx = index_map(gi, gj, gk)
            for bidx, bsz, ext in zip(idx, blk, extents):
                if bsz is None:
                    continue      # whole-axis (trit-plane) dimension
                if bidx < 0 or (bidx + 1) * bsz > ext:
                    findings.append(Finding(
                        PASS, "BM003", cell,
                        f"{name} index map at grid ({gi},{gj},{gk}) "
                        f"out of bounds"))
    findings += _check_cim_clip_window(cell)
    if not findings and m <= 32 and k <= 256 and n <= 256:
        findings += _check_cim_abstract_eval(cell, m, k, n, bm, bn, bk)
    return findings


def run(pin_blocks: Optional[tuple] = None) -> list:
    """The full blockmap pass over the shape sweep (every packing x
    domain cell of both ternary kernels, plus the cim kernel).
    ``pin_blocks`` overrides the selector everywhere — the violation
    injection the CLI exposes as ``--pin-blocks BM,BN,BK``."""
    findings = []
    for m, k, n in SHAPE_SWEEP:
        for mode in ("base3", "trit2"):
            for domain in ("float", "int8"):
                findings += check_ternary_cell(m, k, n, mode, domain,
                                               blocks=pin_blocks)
    for m, k, n in ((1, 13, 50), (8, 160, 64), (16, 256, 256),
                    (100, 4096, 16)):
        findings += check_cim_cell(m, k, n, blocks=pin_blocks)
    return findings

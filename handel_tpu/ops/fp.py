"""JAX prime-field arithmetic on limb vectors — the TPU's bignum engine.

This layer replaces the reference's native field arithmetic (the amd64/arm64
assembly inside its cloudflare/bn256 dependency, SURVEY.md §2.2) with
TPU-friendly kernels. It is the risk item called out in SURVEY.md §7 hard part
(a).

Design:

  * **Limbs-major layout.** An Fp element batch is a uint32 array of shape
    (NLIMBS, B): limb index first, batch last, so every limb operation is a
    vector op over the batch — with batch-last, a 16-limb element would
    occupy 16/128 lanes. Inside the multiplication kernel a limb ROW is
    handled as (8, 128) tiles of the batch, a whole vector register each
    (`mul_tile`): as a (B,) row it would use one sublane in eight.
  * **16-bit limbs in uint32 lanes.** Limb products fit uint32 exactly (no
    mul-high needed) and anti-diagonal column sums of split lo/hi halves stay
    < 2^23, so carries are propagated lazily once per multiplication.
  * **Montgomery multiplication** (radix 2^16, CIOS-style column interleave)
    as one fused Pallas kernel: inputs stream HBM->VMEM in (NLIMBS, block)
    blocks, all ~n^2 limb products and column sums happen in VMEM/registers.
    Its speed on the chip is the benchmark's to say (`fp_mul.mac_rate`,
    PERF.md section 3). The naive XLA graph this replaces materializes
    (B,16,16) intermediates through HBM.
  * **Batch stacking beats vmap.** Callers (ops/tower.py) flatten independent
    field muls into the batch dimension (one Fp12 mul = ONE mont_mul call at
    54x batch), keeping lanes full even for small pairing batches.
  * A pure-XLA fallback with identical semantics runs where Pallas TPU kernels
    aren't available (CPU tests); both paths are cross-validated.

All values are kept canonical (< p) at op boundaries. Elements are in
Montgomery form except where a method says otherwise; the Montgomery
constant is backend-specific (R = 2^(16*NLIMBS) for CIOS, the base-A
product M for RNS) but canonical non-Montgomery boundary values are
bit-identical across backends.

**Backend seam.** `Field(p, backend=...)` selects the modmul kernel:

  * ``backend="cios"`` (default) — this module's CIOS kernel above.
  * ``backend="rns"`` — `ops/rns.py`'s residue-number-system Montgomery
    pipeline, which restructures the multiply into constant-matrix
    `dot_general` contractions so the MXU (idle under CIOS — contraction
    depth 1) carries the bulk work. `Field.__new__`
    redirects construction to `RnsField`, a subclass overriding only
    `mul`; everything else here (add/sub/inv/pow/pack/unpack, the
    carry-lookahead machinery) is inherited, and `ops/tower.py`'s
    batch-stacking entry points route through whichever kernel the
    constructed Field carries — `BN254Device` dispatch, the fleet plane,
    and the lifecycle/epoch paths inherit the backend transparently.
    Config plumbing: `fp_backend` in the TOML -> SimConfig ->
    models/bn254_jax.py -> ops/curve.py -> here. The CIOS kernel stays
    the bit-exact oracle (tests/test_fp_jax.py, scripts/rns_smoke.py).

    On top of the per-mul pipeline the RNS backend exposes a **resident
    value form** (`RnsField.to_resident`/`mul_resident`/`from_resident`
    plus the `ResidentRns` Field-shaped adapter): values stay as joint
    residue planes across whole tower formulas, and the CRT
    reconstruction that `RnsField.mul` pays at every call is deferred to
    genuine pairing boundaries (point coordinates entering the Miller
    loop, the final GT verdict). `ops/pairing.py` threads that form
    through the Miller loop and the final-exponentiation tower when the
    backend is RNS (opt out via `rns_resident`); subtraction sites carry
    static per-site bound literals (`blog`, accepted-and-ignored by the
    CIOS `sub`/`neg` above) — HACKING.md "Residue-resident pairing" has
    the bound algebra.

Correctness oracle: ops/bn254_ref.py; property tests in tests/test_fp_jax.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LIMB_BITS = 16
LIMB_MASK = (1 << LIMB_BITS) - 1

# a uint32 vector register is a tile of 8 sublanes x 128 lanes; batches are
# tiled to 128 lanes
_LANE = 128
# sublanes a limb row of the multiplication kernel fills where the call has
# that many, and the lanes a pass of its body then computes
MUL_ROW_SUBLANES = 8
MUL_STEP_LANES = MUL_ROW_SUBLANES * _LANE
# passes a grid step's block holds at most: what scripts/fp_mul_sweep.py read
# fastest, or within 1 % of it, from 4 608 lanes up in both fields
_MUL_BLOCK_STEPS = 4


def mul_tile(width: int) -> tuple[int, int]:
    """How `fp_mul_<limbs>x<width>` walks its lanes, from the width alone
    (a multiple of 128): `(sublanes, block)`.

    A pass of the kernel's body (`Field._mul_cols`) computes limb rows that
    are `(sublanes, 128)` tiles: 8 sublanes, a whole vector register a row,
    wherever the call has that many (a 128-lane call has one, and its rows
    stay `(128,)`). A grid step moves `block` lanes, `_MUL_BLOCK_STEPS`
    passes' worth or the call's whole width rounded up to passes, whichever
    is less, and a loop in the kernel walks them. Where the block does not
    divide the width the last block is partial, and the loop stops after the
    last pass that holds lanes of the call. Readings: PERF.md section 6,
    PR 39."""
    sublanes = min(width // _LANE, MUL_ROW_SUBLANES)
    step = sublanes * _LANE
    return sublanes, min(-(-width // step), _MUL_BLOCK_STEPS) * step


def _int_to_limbs(x: int, nlimbs: int) -> np.ndarray:
    out = np.zeros(nlimbs, dtype=np.uint32)
    for i in range(nlimbs):
        out[i] = (x >> (LIMB_BITS * i)) & LIMB_MASK
    assert x >> (LIMB_BITS * nlimbs) == 0, "value too large for limb count"
    return out


def _limbs_to_int(limbs) -> int:
    limbs = np.asarray(limbs)
    return sum(int(v) << (LIMB_BITS * i) for i, v in enumerate(limbs))


def windowed_pow_digits(e: int, window: int) -> list[int] | None:
    """MSB-first w-bit digit decomposition of a public exponent, or None when
    the exponent is small enough that a direct chain beats the table. Shared
    by Field.pow_const and Tower.f12_pow_const (one copy of the digit
    arithmetic: a window change must not be able to diverge between them)."""
    bits = bin(e)[2:]
    if len(bits) <= window:
        return None
    pad = (-len(bits)) % window
    padded = "0" * pad + bits
    return [int(padded[i : i + window], 2) for i in range(0, len(padded), window)]


@functools.cache
def device_platform() -> str:
    """The platform the kernels lower for — "cpu" or "tpu" — decided once
    from JAX's default backend. Nothing is swallowed: a backend that fails
    to initialise raises here, and an accelerator no kernel in this repo
    was written for is an error, not a TPU."""
    plat = jax.default_backend()
    if plat not in ("cpu", "tpu"):
        raise RuntimeError(
            f"unsupported JAX platform {plat!r}: the field kernels lower "
            "for 'tpu' (Pallas/Mosaic) or 'cpu' (plain XLA) only"
        )
    return plat


def default_pow_window() -> int:
    """The widest digit a pow chain may use on this platform: 4 bits on the
    TPU, 1 (the plain bit scan) on XLA:CPU. The windowed form builds a
    14-multiplication table plus a gather-inside-scan at EVERY pow site, and
    the CPU backend — where only compile time matters (virtual-mesh dryruns,
    CI) — pays for that in compile seconds multiplied across the staged
    sharded executables. The bit scan compiles to the smallest graph; the
    multiplications it executes beyond a window's are irrelevant off-chip.
    Whether a chain on the TPU takes the window is `pow_window`'s call."""
    return 4 if device_platform() == "tpu" else 1


def bit_runs(bits) -> tuple[list[int], int]:
    """A public bit string as runs: for each set bit the number of steps up
    to and including it since the previous set bit, and the steps after the
    last one. A loop over the runs does the per-step work in an inner loop
    and the per-set-bit work once a run, so no step computes what its bit
    would discard (the Miller loop's addition, a pow chain's multiply)."""
    runs, k = [], 0
    for b in bits:
        k += 1
        if b:
            runs.append(k)
            k = 0
    return runs, k


def pow_chain_muls(e: int, window: int) -> int:
    """Multiplications `windowed_pow` executes for the public exponent `e`
    beyond its squarings. The bit scan multiplies on the set bits after the
    top one: popcount(e) - 1. A w-bit window builds its table (2^w - 2) and
    multiplies on every digit step after the first."""
    digits = windowed_pow_digits(e, window) if window > 1 else None
    if digits is None:
        return bin(e).count("1") - 1
    return 2**window - 2 + len(digits) - 1


def pow_window(e: int) -> int:
    """Digit width of the chain for `e`: the bit scan or the platform's
    window (`default_pow_window`), whichever executes fewer multiplications
    (`pow_chain_muls`) — read off the exponent, which is public. Sparse
    exponents scan bits (BLS12-381's |z|: 5 against 29; BN254's U: 27
    against 29); Fermat's p - 2 takes the window (BN254: 77 against 109,
    BLS12-381: 109 against 228)."""
    return min((1, default_pow_window()), key=lambda w: pow_chain_muls(e, w))


def windowed_pow(a, e: int, window: int, mul, sqr, stack, take, select):
    """Left-to-right windowed square-and-multiply, representation-agnostic.

    window<=1 is the plain bit scan, as a loop over the RUNS of the public
    exponent's bits (`bit_runs`): an outer scan over the set bits, each
    step an inner `fori_loop` of the squarings since the last set bit and
    then one multiplication — a zero bit is its squaring and nothing else,
    with no select and no conditional (a taken `lax.cond` costs about as
    much again as the multiplication it would guard on a v5e: PERF.md
    section 6, PR 29). No table, no gather: the compile-cheapest lowering,
    and the cheapest to run for a sparse exponent.

    A w-bit window keeps the squaring count and replaces w bit-steps with
    one digit-step (w sqrs + 1 table mul + 1 select on the rare zero
    digit), cutting executed muls to 2^w-2 + bits/w while the traced graph
    stays scan-sized (the digit-loop body is traced once). `pow_window`
    chooses between the two from the exponent.

    Primitives: mul(a,b), sqr(a); stack(list_of_elems) -> stacked repr;
    take(stacked, traced_idx) -> elem; select(traced_bool, if_true, if_false).
    """
    import jax

    digits = windowed_pow_digits(e, window) if window > 1 else None
    if digits is None:
        bits = bin(e)[3:]
        if len(bits) < 8:  # tiny exponent: direct chain
            acc = a
            for c in bits:
                acc = sqr(acc)
                if c == "1":
                    acc = mul(acc, a)
            return acc

        def square(_, x):
            return sqr(x)

        def run(acc, squarings):
            return mul(jax.lax.fori_loop(0, squarings, square, acc), a), None

        runs, tail = bit_runs(c == "1" for c in bits)
        acc, _ = jax.lax.scan(run, a, jnp.asarray(runs, jnp.int32))
        return jax.lax.fori_loop(0, tail, square, acc) if tail else acc

    # table[k] = a^(k+1), k = 0..2^w-2 (digit 0 lanes select "no mul")
    table = [a]
    for _ in range(2**window - 2):
        table.append(mul(table[-1], a))
    stacked = stack(table)
    acc = table[digits[0] - 1]  # MSB digit is nonzero by construction

    def step(acc, digit):
        for _ in range(window):
            acc = sqr(acc)
        m = take(stacked, jnp.maximum(digit, 1) - 1)
        return select(digit != 0, mul(acc, m), acc), None

    acc, _ = jax.lax.scan(step, acc, jnp.asarray(digits[1:], jnp.uint32))
    return acc


def _has_pallas_tpu() -> bool:
    return device_platform() == "tpu"


class Field:
    """Modular arithmetic over a fixed prime on uint32 limb vectors.

    All jax methods take/return uint32 arrays of shape (nlimbs, B) in
    Montgomery form (except where noted) and are jit/shard-safe. B must be a
    multiple of 128 for the Pallas path; `pad_batch` helps callers comply.

    `backend` selects the modmul kernel: "cios" (this class) or "rns"
    (ops/rns.py — `__new__` redirects construction there). Canonical
    non-Montgomery boundary values are bit-identical across backends.
    """

    backend = "cios"
    # dtype of one batch row; ops/tower.py consults this so its zero/one
    # constructors match the value representation (uint32 positional limbs
    # here, int32 residue rows for ops/rns.py's ResidentRns adapter)
    limb_dtype = jnp.uint32

    def __new__(cls, p: int = 0, use_pallas: bool | None = None,
                backend: str | None = None):
        if cls is Field and backend == "rns":
            from handel_tpu.ops.rns import RnsField  # lazy: avoid cycle

            return super().__new__(RnsField)
        return super().__new__(cls)

    def __init__(self, p: int, use_pallas: bool | None = None,
                 backend: str | None = None):
        if backend not in (None, "cios", "rns"):
            raise ValueError(
                f"unknown Field backend {backend!r}: valid choices are "
                f"'cios' (VPU CIOS kernel, the bit-exact oracle) and 'rns' "
                f"(MXU residue pipeline, ops/rns.py; its residue-resident "
                f"pairing form is toggled by the `rns_resident` knob)"
            )
        self.p = p
        self.nlimbs = (p.bit_length() + LIMB_BITS - 1) // LIMB_BITS
        n = self.nlimbs
        self.mont_r = (1 << (LIMB_BITS * n)) % p
        self.mont_r2 = self.mont_r * self.mont_r % p
        # -p^{-1} mod 2^16: the Montgomery reduction multiplier
        self.n0 = int((-pow(p, -1, 1 << LIMB_BITS)) % (1 << LIMB_BITS))
        self.p_limbs_np = _int_to_limbs(p, n)
        self.p_limbs = jnp.asarray(self.p_limbs_np)
        self.use_pallas = _has_pallas_tpu() if use_pallas is None else use_pallas
        self._pallas_fns: dict = {}

    # -- host-side conversions (not jittable) ------------------------------

    def pack(self, xs, mont: bool = True) -> jnp.ndarray:
        """List of ints -> (nlimbs, len(xs)) limb array (Montgomery by default)."""
        mult = self.mont_r if mont else 1
        arr = np.stack(
            [_int_to_limbs(x % self.p * mult % self.p, self.nlimbs) for x in xs],
            axis=1,
        )
        return jnp.asarray(arr, jnp.uint32)

    def pack_batch_np(self, xs, mont: bool = True, out=None) -> np.ndarray:
        """`pack_batch` stopping at the host: the (nlimbs, len(xs)) uint32
        limb array as numpy (optionally written into a caller-owned `out`
        buffer). The zero-copy launch packer builds signature limbs with
        this and scatters them into its staging buffers, which reach the
        device via ONE explicit `jax.device_put` instead of an implicit
        per-array transfer (models/bn254_jax.py `_pack_sig_limbs`)."""
        mult = self.mont_r if mont else 1
        p = self.p
        lbytes = LIMB_BITS // 8  # LIMB_BITS is byte-aligned by construction
        buf = b"".join(
            (x % p * mult % p).to_bytes(self.nlimbs * lbytes, "little")
            for x in xs
        )
        arr = np.frombuffer(buf, dtype=np.dtype(f"<u{lbytes}")).reshape(
            len(xs), self.nlimbs
        )
        if out is not None:
            out[:, : len(xs)] = arr.T
            return out
        return arr.T.astype(np.uint32)

    def pack_batch(self, xs, mont: bool = True) -> jnp.ndarray:
        """`pack`, array-at-once: one bigint mulmod + `to_bytes` per element
        and a single vectorized byte→limb reinterpretation for the whole
        batch, instead of `_int_to_limbs`'s nlimbs shift/mask Python ops per
        element. Bit-identical output to `pack` (property-tested); this is
        the launch-packing hot path (models/bn254_jax.py `_pack_requests`),
        where per-launch host cost at batch 256 is what it saves."""
        return jnp.asarray(self.pack_batch_np(xs, mont=mont))

    def unpack(self, limbs, mont: bool = True) -> list[int]:
        """(nlimbs, B) limb array -> list of ints (from Montgomery by default)."""
        arr = np.asarray(limbs)
        mult = pow(self.mont_r, -1, self.p) if mont else 1
        return [
            _limbs_to_int(arr[:, k]) * mult % self.p for k in range(arr.shape[1])
        ]

    @staticmethod
    def pad_batch(b: int) -> int:
        """Smallest Pallas-friendly batch >= b."""
        return max(_LANE, (b + _LANE - 1) // _LANE * _LANE)

    def constant(self, x: int, batch: int) -> jnp.ndarray:
        """Montgomery-form constant broadcast to (nlimbs, batch)."""
        limbs = _int_to_limbs(x % self.p * self.mont_r % self.p, self.nlimbs)
        return jnp.broadcast_to(
            jnp.asarray(limbs, jnp.uint32)[:, None], (self.nlimbs, batch)
        )

    # -- shared limb algebra (used by both XLA and Pallas paths) -----------

    def _mul_cols(self, a, b):
        """Full schoolbook product + interleaved Montgomery reduction on
        limbs-major operands; returns canonical limbs in the operands' shape.

        Column magnitudes stay < 2^23 (<= 2n 16-bit terms per column plus
        reduction contributions), so a single lazy carry pass at the end
        suffices. Statically unrolled: no data-dependent control flow. A
        row (`a[i]`) may have any shape: (B,), or the kernel's (S, 128).
        """
        n = self.nlimbs
        zero = jnp.zeros_like(a[0])
        cols = [zero] * (2 * n + 1)
        for i in range(n):
            prod = a[i][None, :] * b  # (n, B), exact 32-bit products
            lo = prod & LIMB_MASK
            hi = prod >> LIMB_BITS
            for j in range(n):
                cols[i + j] = cols[i + j] + lo[j]
                cols[i + j + 1] = cols[i + j + 1] + hi[j]
        n0 = jnp.uint32(self.n0)
        carry = zero
        for i in range(n):
            t = cols[i] + carry
            m = (t * n0) & LIMB_MASK
            for j in range(n):
                mp = m * jnp.uint32(int(self.p_limbs_np[j]))
                mlo = mp & LIMB_MASK
                mhi = mp >> LIMB_BITS
                if j == 0:
                    carry = (t + mlo) >> LIMB_BITS
                else:
                    cols[i + j] = cols[i + j] + mlo
                cols[i + j + 1] = cols[i + j + 1] + mhi
        cols[n] = cols[n] + carry
        out = []
        carry = zero
        for k in range(n, 2 * n):
            t = cols[k] + carry
            out.append(t & LIMB_MASK)
            carry = t >> LIMB_BITS
        # CIOS bound: result < 2p < 2^(16n), so no carry out of the top limb
        return self._cond_sub_p_rows(out)

    def _cond_sub_p_rows(self, rows):
        """Conditionally subtract p from a list of n canonical 16-bit rows."""
        n = self.nlimbs
        borrow = jnp.zeros_like(rows[0], dtype=jnp.int32)
        diff = []
        for i in range(n):
            d = (
                rows[i].astype(jnp.int32)
                - jnp.int32(int(self.p_limbs_np[i]))
                - borrow
            )
            borrow = (d < 0).astype(jnp.int32)
            diff.append((d + (borrow << LIMB_BITS)).astype(jnp.uint32))
        keep = borrow > 0  # borrowed past the top -> value < p -> keep as-is
        out = [jnp.where(keep, rows[i], diff[i]) for i in range(n)]
        return jnp.stack(out)

    def _add_rows(self, a, b):
        n = self.nlimbs
        carry = jnp.zeros_like(a[0])
        out = []
        for i in range(n):
            t = a[i] + b[i] + carry
            out.append(t & LIMB_MASK)
            carry = t >> LIMB_BITS
        return self._cond_sub_p_rows(out)

    def _sub_rows(self, a, b):
        n = self.nlimbs
        borrow = jnp.zeros_like(a[0], dtype=jnp.int32)
        raw = []
        for i in range(n):
            d = a[i].astype(jnp.int32) - b[i].astype(jnp.int32) - borrow
            borrow = (d < 0).astype(jnp.int32)
            raw.append(d + (borrow << LIMB_BITS))
        # if we borrowed past the top, add p back
        need_p = borrow > 0
        carry = jnp.zeros_like(a[0], dtype=jnp.int32)
        out = []
        for i in range(n):
            t = raw[i] + jnp.where(need_p, jnp.int32(int(self.p_limbs_np[i])), 0) + carry
            out.append((t & LIMB_MASK).astype(jnp.uint32))
            carry = t >> LIMB_BITS
        return jnp.stack(out)

    # -- carry-lookahead machinery (bit-packed, fully fusable) --------------
    #
    # The per-limb Python loops above (_add_rows/_cond_sub_p_rows) trace to
    # ~150 primitive ops per field add; a pairing contains tens of thousands
    # of adds, which made XLA tracing/compilation minutes-slow. Shift-based
    # Kogge-Stone was no better: every `pad` becomes its own unfused LLVM
    # kernel on the XLA CPU backend. Instead, per-limb generate/propagate
    # bits are PACKED into one uint32 word per lane and the carry closure is
    # computed with the classic adder identity
    #
    #     carries(A + B) = A ^ B ^ (A + B)   (carry INTO bit i)
    #
    # applied to A = g, B = g|p: maj(g, g|p, c) = g | (p & c), exactly the
    # carry recurrence. ~10 elementwise/reduction ops per add, no data
    # movement, fuses into one kernel on every backend. Requires nlimbs < 32.
    # The unrolled per-limb forms are kept for the Pallas kernel body, where
    # Mosaic wants straight-line register code; they index rows with `a[i]`
    # and take a row of any shape, so the kernel hands them (8, 128) tiles.

    @property
    def _bit_weights(self):
        # plain numpy so it embeds as a fresh constant in every trace
        w = getattr(self, "_bw", None)
        if w is None:
            w = (np.uint64(1) << np.arange(self.nlimbs, dtype=np.uint64)).astype(
                np.uint32
            )[:, None]
            self._bw = w
        return w

    def _carry_word(self, g, p):
        """Closed carry word from per-limb generate/propagate (0/1 uint32
        rows): bit i of the result = carry INTO position i."""
        gb = jnp.sum(g * self._bit_weights, axis=0, dtype=jnp.uint32)
        pb = jnp.sum(p * self._bit_weights, axis=0, dtype=jnp.uint32)
        b = gb | pb
        return (gb + b) ^ gb ^ b

    def _ks_carry(self, s):
        """Normalize (nlimbs, B) limbs with values < 2^17 to canonical 16-bit
        limbs via bit-packed carry-lookahead. Returns (limbs, carry_out)."""
        r = s & LIMB_MASK
        g = s >> LIMB_BITS  # 0/1
        p = (r == LIMB_MASK).astype(jnp.uint32)
        c = self._carry_word(g, p)
        cin = (c[None, :] >> jnp.arange(self.nlimbs, dtype=jnp.uint32)[:, None]) & 1
        out = (r + cin) & LIMB_MASK
        return out, ((c >> self.nlimbs) & 1).astype(bool)

    def _borrow_chain(self, t):
        """Closed borrow bits for int32 limb differences t (t<0 generates a
        borrow, t==0 propagates one). Returns (borrow_in, borrowed_past_top)."""
        g = (t < 0).astype(jnp.uint32)
        p = (t == 0).astype(jnp.uint32)
        c = self._carry_word(g, p)
        bin_ = (c[None, :] >> jnp.arange(self.nlimbs, dtype=jnp.uint32)[:, None]) & 1
        return bin_.astype(jnp.int32), ((c >> self.nlimbs) & 1).astype(bool)

    def _cond_sub_p(self, r):
        """Canonicalize r (< 2p, canonical limbs) to r mod p."""
        t = r.astype(jnp.int32) - jnp.asarray(self.p_limbs_np, jnp.int32)[:, None]
        b, borrowed = self._borrow_chain(t)
        out = ((t - b) & LIMB_MASK).astype(jnp.uint32)
        return jnp.where(borrowed, r, out)  # borrowed past top -> r < p

    # -- public ring ops ----------------------------------------------------

    def add(self, a, b):
        r, _ = self._ks_carry(a + b)  # a, b < p so a+b < 2p < 2^256
        return self._cond_sub_p(r)

    def sub(self, a, b, blog: int | None = None):
        """a - b mod p. `blog` is the resident-form subtrahend bound knob
        (ops/rns.py `ResidentRns.sub`): canonical positional limbs are always
        < p, so the CIOS kernel ignores it — accepting the parameter keeps
        ops/tower.py's per-site bound literals backend-agnostic."""
        t = a.astype(jnp.int32) - b.astype(jnp.int32)
        bor, borrowed = self._borrow_chain(t)
        raw = ((t - bor) & LIMB_MASK).astype(jnp.uint32)  # a-b mod 2^256
        # if a < b, add p back
        padd = jnp.where(
            borrowed, jnp.asarray(self.p_limbs_np, jnp.uint32)[:, None], 0
        )
        r, _ = self._ks_carry(raw + padd)
        return r

    def neg(self, a, blog: int | None = None):
        return self.sub(jnp.zeros_like(a), a, blog)

    def mul(self, a, b):
        """Montgomery product. Pallas kernel on TPU, pure XLA elsewhere."""
        if self.use_pallas:
            return self._mul_pallas(a, b)
        return self._mul_cols_vec(a, b)

    def _mul_cols_vec(self, a, b):
        """Same CIOS Montgomery product as `_mul_cols`, but expressed with
        (n, n, B) tensor ops and slice-updates instead of fully unrolled
        per-limb scalar graphs.

        Rationale: `_mul_cols` unrolls to ~n^2*6 primitive ops, which is what
        the Pallas kernel wants (Mosaic compiles it to tight VPU code) but
        makes plain-XLA compilation of pairing-sized graphs minutes-slow on
        CPU. This form is ~6x fewer HLO ops with identical semantics; both
        paths are cross-validated in tests/test_fp_jax.py.
        """
        n = self.nlimbs
        bsz = a.shape[1]
        t = a[:, None, :] * b[None, :, :]  # (n, n, B); 16x16-bit products, exact
        lo = t & LIMB_MASK
        hi = t >> LIMB_BITS
        cols = jnp.zeros((2 * n + 1, bsz), jnp.uint32)
        for i in range(n):
            cols = cols.at[i : i + n].add(lo[i])
            cols = cols.at[i + 1 : i + n + 1].add(hi[i])
        # interleaved Montgomery reduction (identical column algebra to
        # _mul_cols: per-column magnitudes stay < 2^23, one lazy carry pass)
        n0 = jnp.uint32(self.n0)
        p_col = jnp.asarray(self.p_limbs_np, jnp.uint32)[:, None]  # (n, 1)
        carry = jnp.zeros((bsz,), jnp.uint32)
        for i in range(n):
            t0 = cols[i] + carry
            m = (t0 * n0) & LIMB_MASK
            mp = m[None, :] * p_col  # (n, B)
            mlo = mp & LIMB_MASK
            mhi = mp >> LIMB_BITS
            carry = (t0 + mlo[0]) >> LIMB_BITS
            cols = cols.at[i + 1 : i + n].add(mlo[1:])
            cols = cols.at[i + 1 : i + n + 1].add(mhi)
        cols = cols.at[n].add(carry)
        hi = cols[n : 2 * n]  # column values < 2^23 (CIOS bound)
        spill = jnp.pad(hi >> LIMB_BITS, ((1, 0), (0, 0)))[:n]  # multi-bit carries
        r, _ = self._ks_carry((hi & LIMB_MASK) + spill)
        return self._cond_sub_p(r)

    def sqr(self, a):
        return self.mul(a, a)

    def _mul_pallas(self, a, b):
        """`_mul_cols` as the Mosaic call `fp_mul_<limbs>x<lanes>` over 2-D
        `u32[limbs, lanes]` operands: a grid over blocks of lanes, each
        computed in passes whose limb rows are register tiles (`mul_tile`
        says how many sublanes and how wide a block, from the width)."""
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        n = self.nlimbs
        bsz = a.shape[1]
        if bsz == 0:  # empty slices show up inside associative_scan
            return jnp.zeros_like(a)
        if bsz % _LANE != 0:
            # odd widths appear inside library combinators (e.g. the interior
            # slices of associative_scan): zero-pad to the lane granularity
            # and slice back — Montgomery 0*0 = 0 stays canonical
            padded = self.pad_batch(bsz)
            pad = lambda x: jnp.pad(x, ((0, 0), (0, padded - bsz)))
            return self._mul_pallas(pad(a), pad(b))[:, :bsz]
        fn = self._pallas_fns.get(bsz)
        if fn is None:
            sublanes, block = mul_tile(bsz)
            step = sublanes * _LANE
            # a limb row as a (sublanes, 128) tile fills its registers; as a
            # (step,) row it would take one sublane of each
            tile = (n, sublanes, _LANE) if sublanes > 1 else (n, _LANE)

            def kernel(a_ref, b_ref, o_ref):
                def rows(at):
                    out = self._mul_cols(
                        a_ref[:, at].reshape(tile), b_ref[:, at].reshape(tile))
                    o_ref[:, at] = out.reshape(n, step)

                if block == step:
                    rows(slice(None))
                    return

                def one(k, carry):
                    rows(pl.ds(pl.multiple_of(k * step, step), step))
                    return carry

                steps = block // step
                if bsz % block:  # the last block is partial
                    left = pl.cdiv(bsz, step) - pl.program_id(0) * steps
                    steps = jnp.minimum(steps, left)
                jax.lax.fori_loop(0, steps, one, 0)

            spec = pl.BlockSpec(
                (n, block), lambda i: (0, i), memory_space=pltpu.VMEM)
            fn = pl.pallas_call(
                kernel,
                # operation and stacked width, so a profiler trace tells
                # the multiplications of one phase from another's
                name=f"fp_mul_{n}x{bsz}",
                out_shape=jax.ShapeDtypeStruct((n, bsz), jnp.uint32),
                grid=(pl.cdiv(bsz, block),),
                in_specs=[spec, spec],
                out_specs=spec,
            )
            self._pallas_fns[bsz] = fn
        return fn(a, b)

    # -- derived ops --------------------------------------------------------

    def pow_const(self, a, e: int, window: int | None = None):
        """a^e for a fixed public exponent: windowed square-and-multiply
        (`windowed_pow`), the digit width read off the exponent
        (`pow_window`) — for the 254-bit Fermat inversion, the window's 77
        executed muls instead of the bit scan's 109 on accelerators; plain
        bit scan on CPU where compile time dominates (default_pow_window)."""
        return windowed_pow(
            a,
            e,
            pow_window(e) if window is None else window,
            mul=self.mul,
            sqr=lambda x: self.mul(x, x),
            stack=lambda t: jnp.stack(t),
            take=lambda s, i: s[i],
            select=lambda c, x, y: jnp.where(c, x, y),
        )

    def inv(self, a):
        """Field inverse by Fermat: a^(p-2). Zero maps to zero."""
        return self.pow_const(a, self.p - 2)

    def select(self, mask, a, b):
        """Per-element select: mask (B,) bool -> limbs from a else b."""
        return jnp.where(mask[None, :], a, b)

    def is_zero(self, a):
        return jnp.all(a == 0, axis=0)

    def eq(self, a, b):
        return jnp.all(a == b, axis=0)

    # -- Montgomery domain conversions (jittable) ---------------------------

    def to_mont(self, a):
        r2 = jnp.broadcast_to(
            jnp.asarray(_int_to_limbs(self.mont_r2, self.nlimbs), jnp.uint32)[
                :, None
            ],
            a.shape,
        )
        return self.mul(a, r2)

    def from_mont(self, a):
        one = jnp.zeros_like(a).at[0].set(1)
        return self.mul(a, one)

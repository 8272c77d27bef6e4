"""BLS-over-BLS12-381 with verification on the JAX/TPU path.

The second device curve behind the Constructor interface — where the
reference offers two interchangeable BN256 backends (bn256/go, bn256/cf)
dispatched by the curve registry (simul/lib/config.go:211-225), this
framework offers interchangeable PAIRING CURVES and KEY ORIENTATIONS on the
device path, sharing one launch engine:

  * `bn254-jax`            BN254, keys in G2, signatures in G1;
  * `bls12-381-jax`        BLS12-381, keys in G2, signatures in G1
                           (the BLS draft's minimal-signature-size);
  * `bls12-381-minpk-jax`  BLS12-381, keys in G1, signatures in G2
                           (minimal-pubkey-size, `BLS_SIG_BLS12381G2_*`).

All machinery — dense masked-sum kernel, prefix-table O(1) range path,
padded fixed-shape launches, async adapter — is inherited from
models/bn254_jax.py `BN254Device`; this module only binds the BLS12-381
curve family (381-bit field, M-type twist, |z|-bit Miller loop), the group
the registry keys live in, and the host wire formats of models/bls12_381.py.
"""

from __future__ import annotations

from handel_tpu.models.bls12_381 import (
    BLS12381Constructor,
    BLS12381Scheme,
    MinPkConstructor,
    MinPkScheme,
    hash_to_g1,
    hash_to_g2,
)
from handel_tpu.models.bn254_jax import (
    BN254Device,
    BN254JaxConstructor,
    BN254JaxScheme,
)
from handel_tpu.ops import bls12_381_ref as bls
from handel_tpu.ops.curve import BLS12Curves
from handel_tpu.ops.pairing import BLS12Pairing


class BLS12381Device(BN254Device):
    """BLS12-381 binding of the device verification engine."""

    ref = bls
    Curves = BLS12Curves
    Pairing = BLS12Pairing
    _hash_to_sig_group = staticmethod(hash_to_g1)


class BLS12381MinPkDevice(BLS12381Device):
    """The same curve in the other group binding: registry keys, prefix
    table and hole patch in G1, signatures and H(m) in G2. The per-candidate
    check on one chip with the cios field; `batch_check="rlc"`,
    `mesh_devices > 1` and `fp_backend="rns"` are refused at construction
    (models/bn254_jax.py `BN254Device.__init__`)."""

    key_group = 1
    _hash_to_sig_group = staticmethod(hash_to_g2)


# The constructors and schemes below take their options from the BN254 ones
# they inherit (`BN254JaxConstructor.__init__`, `BN254JaxScheme.__init__`):
# one list of options for every device scheme.


class BLS12381JaxConstructor(BLS12381Constructor, BN254JaxConstructor):
    """Constructor whose `batch_verify` runs on the JAX/TPU path; wire
    formats and single-sig verify stay the host BLS12-381 scheme's."""

    Device = BLS12381Device


class BLS12381JaxScheme(BLS12381Scheme):
    """Keygen facade for harness/simulation use: the host scheme's keygen and
    wire formats with the device-verification constructor swapped in."""

    Constructor = BLS12381JaxConstructor
    __init__ = BN254JaxScheme.__init__


class BLS12381MinPkJaxConstructor(MinPkConstructor, BN254JaxConstructor):
    """The minimal-pubkey-size host scheme's wire formats over the
    G1-keyed device engine."""

    Device = BLS12381MinPkDevice


class BLS12381MinPkJaxScheme(MinPkScheme):
    Constructor = BLS12381MinPkJaxConstructor
    __init__ = BN254JaxScheme.__init__

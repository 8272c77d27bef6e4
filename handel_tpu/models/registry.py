"""String -> signature-scheme dispatch.

Reference: simul/lib/config.go:211-225 (`Config.NewConstructor`: "bn256",
"bn256/cf", "bn256/go"). Here the names select both keygen and the verify
path; the "-jax" schemes verify on device.

One table holds every alias: canonical name -> (is_device, factory). Keeping
`is_device_scheme` and `new_scheme` on the same table means a new alias
can't silently miss the batch-size plumbing in sim/node.py.
"""

from __future__ import annotations


def _fake(**kw):
    from handel_tpu.models.fake import FakeScheme

    return FakeScheme()


def _bn254(**kw):
    from handel_tpu.models.bn254 import BN254Scheme

    return BN254Scheme()


def _bn254_jax(**kw):
    from handel_tpu.models.bn254_jax import BN254JaxScheme

    return BN254JaxScheme(**kw)


def _eddsa(**kw):
    from handel_tpu.models.eddsa import EdDSAScheme

    return EdDSAScheme()


def _bls12_381(**kw):
    from handel_tpu.models.bls12_381 import BLS12381Scheme

    return BLS12381Scheme()


def _bls12_381_jax(**kw):
    from handel_tpu.models.bls12_381_jax import BLS12381JaxScheme

    return BLS12381JaxScheme(**kw)


def _bls12_381_minpk(**kw):
    from handel_tpu.models.bls12_381 import MinPkScheme

    return MinPkScheme()


def _bls12_381_minpk_jax(**kw):
    from handel_tpu.models.bls12_381_jax import BLS12381MinPkJaxScheme

    return BLS12381MinPkJaxScheme(**kw)


# alias -> (is_device_scheme, factory)
_TABLE = {
    "fake": (False, _fake),
    "empty": (False, _fake),
    "bn254": (False, _bn254),
    "bn256": (False, _bn254),
    "bn254-ref": (False, _bn254),
    "bn254-jax": (True, _bn254_jax),
    "bn254-tpu": (True, _bn254_jax),
    "bn256-tpu": (True, _bn254_jax),
    "eddsa": (False, _eddsa),
    "ed25519": (False, _eddsa),
    "bls12-381": (False, _bls12_381),
    "bls12381": (False, _bls12_381),
    "bls12-381-jax": (True, _bls12_381_jax),
    "bls12-381-tpu": (True, _bls12_381_jax),
    "bls12381-jax": (True, _bls12_381_jax),
    # the same curve, keys in G1 and signatures in G2 (the BLS draft's
    # minimal-pubkey-size; `bls12-381` is minimal-signature-size)
    "bls12-381-minpk": (False, _bls12_381_minpk),
    "bls12-381-minpk-jax": (True, _bls12_381_minpk_jax),
}

SCHEMES = (
    "fake", "bn254", "bn254-jax", "eddsa", "bls12-381", "bls12-381-jax",
    "bls12-381-minpk", "bls12-381-minpk-jax",
)


def new_scheme(name: str, **kwargs):
    entry = _TABLE.get(name.lower())
    if entry is None:
        raise ValueError(f"unknown signature scheme: {name!r}")
    return entry[1](**kwargs)


# device factory -> the host scheme it is a keygen facade over
_HOST_OF = {
    _bn254_jax: _bn254,
    _bls12_381_jax: _bls12_381,
    _bls12_381_minpk_jax: _bls12_381_minpk,
}


def new_keygen_scheme(name: str):
    """The scheme an orchestrating PARENT uses for keygen and registry I/O.

    Device schemes are facades over their host scheme (same keys, same wire
    formats; only `constructor` differs), and building one initialises a
    JAX backend — which takes the chip from the child process that is meant
    to own it. The host scheme never imports jax."""
    entry = _TABLE.get(name.lower())
    if entry is None:
        raise ValueError(f"unknown signature scheme: {name!r}")
    return _HOST_OF.get(entry[1], entry[1])()


def is_device_scheme(name: str) -> bool:
    """True when `name` selects a device-verification scheme (one whose
    constructor accepts batch_size and exposes a Device class)."""
    entry = _TABLE.get(name.lower())
    return bool(entry and entry[0])

"""The benchmark's plain reference: BLS12-381 signatures on the host.

`fields`, `curves`, `pairing` and `hash_to_curve` are copies of the
repository's host oracle (`lodestar_tpu/crypto/`), kept here so that the
comparison that decides `correct` imports nothing of the program it
judges.  They are straightforward Python-int arithmetic, independent of
the device kernels.  `bls` adds the few operations the benchmark needs:
key generation, signing a 32-byte root with a scalar, and the
verification of one signature set from its wire bytes.
"""

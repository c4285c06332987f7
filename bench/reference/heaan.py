"""Plain reference for the served HEAAN ciphertext ops.

The same arithmetic the server promises, written again from the scheme's
definition and nothing else: a ciphertext is a pair (ax, bx) of
polynomials in Z[X]/(X^N + 1) with coefficients mod q = 2^logq, held as
little-endian 32-bit words. Products are exact integer negacyclic
convolutions; everything else (masking, the key switch's rounding
division by Q = 2^logQ, rescaling, the Galois automorphism) is Python
integer arithmetic on each coefficient.

It imports nothing of the program. Products use a float64 FFT over
8-bit digits: an output digit is a sum of at most N·D products of bytes
(under 2^40 at Table III), so float64 leaves each one within a small
fraction of an integer; every product checks that distance and raises
where it is not small. ``cdtype=np.complex64`` is
the same computation one precision lower, which is the benchmark's
control: it must come out wrong.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import scipy.fft as sfft

__all__ = ["Ct", "Reference", "ReferenceError_", "negacyclic_product",
           "words_to_ints", "ints_to_words"]

# an output digit farther than this from an integer means the float
# computation lost the exact result
MAX_ROUNDING = 0.25
FFT_WORKERS = 8


class ReferenceError_(RuntimeError):
    """The reference could not compute an exact result."""


@dataclasses.dataclass
class Ct:
    """A ciphertext as the reference sees it: (N, words) uint32 arrays."""
    ax: np.ndarray
    bx: np.ndarray
    logq: int
    logp: int


def words_for(bits: int) -> int:
    return -(-bits // 32)


def words_to_ints(w: np.ndarray) -> np.ndarray:
    """(N, W) little-endian uint32 words -> object array of N ints."""
    raw = np.ascontiguousarray(w, dtype="<u4")
    return np.array([int.from_bytes(row.tobytes(), "little") for row in raw],
                    dtype=object)


def ints_to_words(v: np.ndarray, bits: int) -> np.ndarray:
    """Object array of N non-negative ints < 2^bits -> (N, W) uint32."""
    nb = 4 * words_for(bits)
    buf = b"".join(int(x).to_bytes(nb, "little") for x in v)
    return np.frombuffer(buf, dtype="<u4").reshape(len(v), -1).copy()


def _digits(v: np.ndarray, bits: int) -> np.ndarray:
    """Object array of ints (taken mod 2^bits) -> (N, ceil(bits/8)) bytes."""
    nb = -(-bits // 8)
    mask = (1 << bits) - 1
    buf = b"".join((int(x) & mask).to_bytes(nb, "little") for x in v)
    return np.frombuffer(buf, dtype=np.uint8).reshape(len(v), nb)


class _Spectra:
    """Spectra of byte-digit polynomials at one transform size.

    Axis 1 (digits) is a real FFT of length ``length`` ≥ da + db − 1, so
    digit products never wrap. Axis 0 (powers of X) is a complex FFT of
    the input twisted by ω^k, ω = e^{iπ/N}: a cyclic convolution of
    twisted sequences is the twisted negacyclic one.
    """

    def __init__(self, n: int, length: int, cdtype):
        self.length = length
        self.cdtype = cdtype
        self.fdtype = np.float64 if cdtype == np.complex128 else np.float32
        self.twist = np.exp(1j * np.pi * np.arange(n) / n).astype(cdtype)

    def forward(self, digits: np.ndarray) -> np.ndarray:
        n, d = digits.shape
        x = np.zeros((n, self.length), self.fdtype)
        x[:, :d] = digits
        f = sfft.rfft(x, axis=1, workers=FFT_WORKERS)
        del x
        f *= self.twist[:, None]
        return sfft.fft(f, axis=0, workers=FFT_WORKERS, overwrite_x=True)

    def inverse(self, spec: np.ndarray, out_bits: int) -> np.ndarray:
        """Spectrum of a product -> object array of coefficients mod
        2^out_bits. Raises where a float64 result is not near integers;
        a lower precision (the control) is rounded as it comes."""
        f = sfft.ifft(spec, axis=0, workers=FFT_WORKERS)
        f *= np.conj(self.twist)[:, None]
        re = sfft.irfft(f, n=self.length, axis=1, workers=FFT_WORKERS)
        del f
        v = np.rint(re)
        if self.cdtype == np.complex128:
            np.subtract(re, v, out=re)
            err = float(np.max(np.abs(re, out=re)))
            if not err < MAX_ROUNDING:
                raise ReferenceError_(
                    f"FFT product off an integer by {err:.3f}")
        del re
        nd = -(-out_bits // 8)
        # digit positions along rows, so the carry walks contiguous rows
        vt = np.ascontiguousarray(v[:, :nd].T).astype(np.int64)
        del v
        out = np.zeros((nd, vt.shape[1]), np.uint8)
        carry = np.zeros(vt.shape[1], np.int64)
        for p in range(nd):
            t = vt[p] + carry if p < len(vt) else carry
            out[p] = t & 0xFF
            carry = t >> 8
        out = np.ascontiguousarray(out.T)
        mask = (1 << out_bits) - 1
        return np.array([int.from_bytes(row.tobytes(), "little") & mask
                         for row in out], dtype=object)


def _fft_length(da: int, db: int) -> int:
    """Smallest even 2^a·3^b·5^c at least da + db − 1 (no digit wrap)."""
    need = da + db - 1
    return min((1 << a) * 3 ** b * 5 ** c
               for a in range(1, 12) for b in range(8) for c in range(6)
               if (1 << a) * 3 ** b * 5 ** c >= need)


def negacyclic_product(a: np.ndarray, a_bits: int, b: np.ndarray,
                       b_bits: int, out_bits: int, *,
                       cdtype=np.complex128) -> np.ndarray:
    """(a·b mod X^N + 1) mod 2^out_bits, for object arrays of ints.

    a and b are taken mod 2^a_bits and 2^b_bits; only their low out_bits
    bits can reach the low out_bits bits of the product, so callers pass
    at most that.
    """
    da, db = _digits(a, a_bits), _digits(b, b_bits)
    sp = _Spectra(len(a), _fft_length(da.shape[1], db.shape[1]), cdtype)
    return sp.inverse(sp.forward(da) * sp.forward(db), out_bits)


def _round_shift(v: np.ndarray, s: int) -> np.ndarray:
    """floor((v + 2^(s−1)) / 2^s): division by 2^s, halves rounded up."""
    half = 1 << (s - 1)
    return np.array([(int(x) + half) >> s for x in v], dtype=object)


def _mask(v: np.ndarray, bits: int) -> np.ndarray:
    m = (1 << bits) - 1
    return np.array([int(x) & m for x in v], dtype=object)


def _add_mod(x: np.ndarray, y: np.ndarray, bits: int) -> np.ndarray:
    m = (1 << bits) - 1
    return np.array([(int(a) + int(b)) & m for a, b in zip(x, y)],
                    dtype=object)


def automorphism(v: np.ndarray, k: int, logq: int) -> np.ndarray:
    """t(X) -> t(X^k) in Z_q[X]/(X^N + 1): X^i goes to ±X^(i·k mod N)."""
    n = len(v)
    q = 1 << logq
    out = np.empty(n, dtype=object)
    for i, x in enumerate(v):
        j = (i * k) % (2 * n)
        out[j % n] = (q - int(x)) % q if j >= n else int(x)
    return out


class Reference:
    """The served ops at one parameter set, over one set of keys.

    keys: {"evk": (ax, bx), ("rot", r): (ax, bx)} with each part an
    (N, words) uint32 array mod Q² = 2^(2·logQ). The spectra of a key are
    kept once made, since every sample at a level switches with the same
    key. ``cdtype=np.complex64`` computes every product one precision
    lower: the control.
    """

    def __init__(self, logQ: int, keys: dict, *, cdtype=np.complex128):
        self.logQ = logQ
        self.cdtype = cdtype
        self._keys = {name: tuple(words_to_ints(w) for w in pair)
                      for name, pair in keys.items()}
        self._spectra = {}

    def _key_switch(self, d: np.ndarray, key, logq: int) -> tuple:
        """(round(d·key.ax / Q), round(d·key.bx / Q)) mod q.

        The quotient mod 2^logq depends only on the product mod
        2^(logq + logQ), so the key (mod Q²) is cut to those bits.
        """
        bits = logq + self.logQ
        n_d, n_k = -(-logq // 8), -(-bits // 8)
        sp = _Spectra(len(d), _fft_length(n_d, n_k), self.cdtype)
        if (key, logq) not in self._spectra:
            self._spectra[(key, logq)] = [
                sp.forward(_digits(part, bits)) for part in self._keys[key]]
        fd = sp.forward(_digits(d, logq))
        return tuple(
            _mask(_round_shift(sp.inverse(fd * fk, bits), self.logQ), logq)
            for fk in self._spectra[(key, logq)])

    def mul(self, c1: Ct, c2: Ct) -> Ct:
        """HEAAN HE Mul: tensor product, then relinearise with evk.

        With d0 = b1·b2, d1 = a1·b2 + a2·b1, d2 = a1·a2 (mod q):
        ax = d1 + round(d2·evk.ax / Q), bx = d0 + round(d2·evk.bx / Q).
        """
        logq = c1.logq
        sp = _Spectra(c1.ax.shape[0], _fft_length(-(-logq // 8),
                                                  -(-logq // 8)),
                      self.cdtype)
        fa1, fb1, fa2, fb2 = (sp.forward(_digits(words_to_ints(w), logq))
                              for w in (c1.ax, c1.bx, c2.ax, c2.bx))
        d0 = sp.inverse(fb1 * fb2, logq)
        d2 = sp.inverse(fa1 * fa2, logq)
        d1 = sp.inverse(fa1 * fb2 + fa2 * fb1, logq)
        del fa1, fb1, fa2, fb2
        ks_ax, ks_bx = self._key_switch(d2, "evk", logq)
        return Ct(ints_to_words(_add_mod(d1, ks_ax, logq), logq),
                  ints_to_words(_add_mod(d0, ks_bx, logq), logq),
                  logq, c1.logp + c2.logp)

    def mul_plain(self, c: Ct, pt: np.ndarray, pt_logp: int) -> Ct:
        """Ciphertext × plaintext: (ax·pt, bx·pt) mod q; no key switch."""
        logq = c.logq
        nd = -(-logq // 8)
        sp = _Spectra(c.ax.shape[0], _fft_length(nd, nd), self.cdtype)
        fp = sp.forward(_digits(words_to_ints(pt), logq))
        ax, bx = (sp.inverse(sp.forward(_digits(words_to_ints(w), logq))
                             * fp, logq) for w in (c.ax, c.bx))
        return Ct(ints_to_words(ax, logq), ints_to_words(bx, logq), logq,
                  c.logp + pt_logp)

    def rotate(self, c: Ct, r: int) -> Ct:
        """Left-rotate the slots by r: apply σ_k, k = 5^r mod 2N, to both
        parts, then switch σ_k(ax) back to the secret with the rotation
        key: ax = round(σ(a)·rk.ax / Q), bx = σ(b) + round(σ(a)·rk.bx / Q).
        """
        logq = c.logq
        k = pow(5, r, 2 * c.ax.shape[0])
        a = automorphism(words_to_ints(c.ax), k, logq)
        b = automorphism(words_to_ints(c.bx), k, logq)
        ks_ax, ks_bx = self._key_switch(a, ("rot", r), logq)
        return Ct(ints_to_words(ks_ax, logq),
                  ints_to_words(_add_mod(b, ks_bx, logq), logq), logq,
                  c.logp)

    @staticmethod
    def rescale(c: Ct, dlogp: int) -> Ct:
        """Divide by 2^dlogp: centre each coefficient in [−q/2, q/2),
        divide with halves rounded up, reduce mod 2^(logq − dlogp)."""
        logq = c.logq
        q, half_q = 1 << logq, 1 << (logq - 1)
        out_bits = logq - dlogp

        def one(w):
            centred = np.array([x - q if x >= half_q else x
                                for x in words_to_ints(w)], dtype=object)
            return ints_to_words(_mask(_round_shift(centred, dlogp),
                                       out_bits), out_bits)

        return Ct(one(c.ax), one(c.bx), out_bits, c.logp - dlogp)

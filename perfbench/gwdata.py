"""Synthetic whitened, band-passed detector strain from a seed.

Copied from the program's ``data/gw.py`` (analytic aLIGO-like PSD,
Newtonian inspiral chirp, whitening by the amplitude spectral density,
FFT band-pass, one global normalisation scale) and kept here, so that the
benchmark's traffic cannot change with the program.  Where ``data/gw.py``
cuts single windows out of one-second segments, ``strain`` makes
continuous strain of any length: what a detector stream or an archive
holds.  Everything is numpy on the host, deterministic per generator.
"""

from __future__ import annotations

import numpy as np


def analytic_psd(freqs: np.ndarray) -> np.ndarray:
    """aLIGO-like analytic one-sided PSD (arbitrary overall scale): seismic
    wall clamped at 20 Hz, suspension ~ f^-4, flat floor, shot rise ~ f^2."""
    f = np.maximum(np.abs(freqs), 20.0)
    x = f / 215.0
    return 1e4 * (20.0 / f) ** 14 + 0.6 * x**-4 + 1.0 + x**2


def inspiral_chirp(n: int, sample_rate: float, f0: float, f1: float,
                   duration: int = 120) -> np.ndarray:
    """Leading-order inspiral ending at sample ``n``: f(t) = f0 (1 -
    t/tc)^(-3/8) capped at f1, amplitude ~ f^(2/3), tapered start."""
    local = np.arange(duration) / duration
    tau = np.maximum(1.0 - local, 1e-3)
    freq = np.minimum(f0 * tau ** (-3.0 / 8.0), f1)
    phase = 2 * np.pi * np.cumsum(freq) / sample_rate
    amp = (freq / f0) ** (2.0 / 3.0)
    ramp = np.minimum(local / 0.2, 1.0)
    h = np.zeros(n, np.float64)
    h[n - duration:] = (amp * np.cos(phase) * ramp)[-min(n, duration):]
    return h


class StrainSource:
    """Whitened, band-passed, globally normalised strain at ``sample_rate``
    with inspiral chirps injected at ``events_per_s`` per stream, each at a
    matched-filter SNR drawn uniformly from ``snr_range``."""

    def __init__(self, sample_rate: float, f_low: float, f_high: float,
                 snr_range: tuple[float, float], events_per_s: float,
                 segment: int = 4096):
        self.sample_rate = sample_rate
        self.f_low, self.f_high = f_low, f_high
        self.snr_range = tuple(snr_range)
        self.events_per_s = events_per_s
        self.segment = segment
        freqs = np.fft.rfftfreq(segment, 1.0 / sample_rate)
        self._asd = np.sqrt(analytic_psd(freqs))
        self._band = (freqs >= f_low) & (freqs <= f_high)
        # colored noise whitened by its own ASD is white noise band-passed:
        # its per-sample variance is the band's share of the spectrum
        self._global_std = float(np.sqrt(self._band.sum() / len(freqs)))
        chirp = inspiral_chirp(segment, sample_rate, f_low, f_high)
        wc = np.fft.irfft(np.fft.rfft(chirp) / self._asd * self._band, segment)
        self._chirp_w = wc / self._global_std
        self._chirp_wnorm = float(np.sqrt(np.sum(wc**2)) + 1e-12)

    def _whitened_noise(self, rng: np.random.Generator, n: int) -> np.ndarray:
        """Detector noise after whitening and band-pass, normalised to unit
        variance: noise coloured by the PSD and whitened by its own ASD is
        white noise band-passed, so that is what is drawn."""
        freqs = np.fft.rfftfreq(n, 1.0 / self.sample_rate)
        band = (freqs >= self.f_low) & (freqs <= self.f_high)
        spec = np.fft.rfft(rng.standard_normal(n)) * band
        return np.fft.irfft(spec, n) / np.sqrt(band.sum() / len(freqs))

    def strain(self, rng: np.random.Generator, n_streams: int,
               n_samples: int) -> np.ndarray:
        """(n_streams, n_samples) float32 strain, deterministic per ``rng``
        state."""
        out = np.empty((n_streams, n_samples), np.float32)
        seconds = n_samples / self.sample_rate
        seg = self.segment
        for s in range(n_streams):
            x = self._whitened_noise(rng, n_samples)
            n_events = rng.poisson(self.events_per_s * seconds)
            for _ in range(n_events):
                end = int(rng.integers(seg, n_samples + 1)) if n_samples >= seg \
                    else n_samples
                snr = rng.uniform(*self.snr_range)
                tmpl = self._chirp_w[-min(seg, end):]
                x[end - len(tmpl): end] += (
                    snr * self._global_std / self._chirp_wnorm) * tmpl
            out[s] = x
        return out

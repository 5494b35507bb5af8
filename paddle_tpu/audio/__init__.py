"""Audio features (reference: python/paddle/audio — spectrograms/mel features).
Implemented with jnp FFT (XLA-compiled on TPU)."""
from __future__ import annotations

import math

import numpy as np

import jax.numpy as jnp

from paddle_tpu.core.tensor import Tensor, apply_op

__all__ = ["functional", "features"]


class functional:
    @staticmethod
    def create_dct(n_mfcc, n_mels, norm="ortho"):
        n = np.arange(n_mels)
        k = np.arange(n_mfcc)[:, None]
        dct = np.cos(math.pi / n_mels * (n + 0.5) * k)
        if norm == "ortho":
            dct[0] *= 1.0 / math.sqrt(2)
            dct *= math.sqrt(2.0 / n_mels)
        return Tensor(jnp.asarray(dct.T.astype(np.float32)))

    @staticmethod
    def hz_to_mel(f, htk=False):
        if htk:
            return 2595.0 * np.log10(1.0 + np.asarray(f) / 700.0)
        f = np.asarray(f, np.float64)
        f_min, f_sp = 0.0, 200.0 / 3
        mels = (f - f_min) / f_sp
        min_log_hz = 1000.0
        min_log_mel = (min_log_hz - f_min) / f_sp
        logstep = np.log(6.4) / 27.0
        with np.errstate(divide="ignore"):
            logpart = min_log_mel + np.log(np.maximum(f, 1e-10) / min_log_hz) / logstep
        return np.where(f >= min_log_hz, logpart, mels)

    @staticmethod
    def mel_to_hz(m, htk=False):
        if htk:
            return 700.0 * (10.0 ** (np.asarray(m) / 2595.0) - 1.0)
        m = np.asarray(m, np.float64)
        f_min, f_sp = 0.0, 200.0 / 3
        freqs = f_min + f_sp * m
        min_log_hz = 1000.0
        min_log_mel = (min_log_hz - f_min) / f_sp
        logstep = np.log(6.4) / 27.0
        return np.where(m >= min_log_mel, min_log_hz * np.exp(logstep * (m - min_log_mel)), freqs)

    @staticmethod
    def compute_fbank_matrix(sr, n_fft, n_mels=64, f_min=0.0, f_max=None, htk=False, norm="slaney"):
        f_max = f_max or sr / 2
        mels = np.linspace(functional.hz_to_mel(f_min, htk), functional.hz_to_mel(f_max, htk), n_mels + 2)
        freqs = functional.mel_to_hz(mels, htk)
        fft_freqs = np.linspace(0, sr / 2, n_fft // 2 + 1)
        weights = np.zeros((n_mels, n_fft // 2 + 1), np.float32)
        for i in range(n_mels):
            lower = (fft_freqs - freqs[i]) / max(freqs[i + 1] - freqs[i], 1e-9)
            upper = (freqs[i + 2] - fft_freqs) / max(freqs[i + 2] - freqs[i + 1], 1e-9)
            weights[i] = np.maximum(0, np.minimum(lower, upper))
        if norm == "slaney":
            enorm = 2.0 / (freqs[2 : n_mels + 2] - freqs[:n_mels])
            weights *= enorm[:, None]
        return Tensor(jnp.asarray(weights))


class features:
    class Spectrogram:
        def __init__(self, n_fft=512, hop_length=None, win_length=None, power=2.0):
            self.n_fft = n_fft
            self.hop = hop_length or n_fft // 4
            self.power = power

        def __call__(self, x: Tensor):
            n_fft, hop, power = self.n_fft, self.hop, self.power

            def f(v):
                frames = []
                n = (v.shape[-1] - n_fft) // hop + 1
                idx = jnp.arange(n)[:, None] * hop + jnp.arange(n_fft)[None]
                fr = v[..., idx] * jnp.hanning(n_fft)
                spec = jnp.abs(jnp.fft.rfft(fr, axis=-1)) ** power
                return jnp.moveaxis(spec, -2, -1)

            return apply_op(f, x, name="spectrogram")

    class MelSpectrogram:
        def __init__(self, sr=16000, n_fft=512, hop_length=None, n_mels=64, f_min=0.0,
                     f_max=None, power=2.0):
            self.spec = features.Spectrogram(n_fft, hop_length, power=power)
            self.fbank = functional.compute_fbank_matrix(sr, n_fft, n_mels, f_min, f_max)

        def __call__(self, x: Tensor):
            s = self.spec(x)
            return apply_op(lambda sv, fb: jnp.einsum("...ft,mf->...mt", sv, fb),
                            s, self.fbank, name="mel")

    class MFCC:
        def __init__(self, sr=16000, n_mfcc=13, n_fft=512, n_mels=64):
            self.mel = features.MelSpectrogram(sr, n_fft, n_mels=n_mels)
            self.dct = functional.create_dct(n_mfcc, n_mels)

        def __call__(self, x: Tensor):
            m = self.mel(x)
            return apply_op(
                lambda mv, d: jnp.einsum("...mt,mk->...kt", jnp.log(mv + 1e-6), d),
                m, self.dct, name="mfcc")

    class LogMelSpectrogram:
        """reference paddle.audio.features.LogMelSpectrogram."""

        def __init__(self, sr=16000, n_fft=512, hop_length=None, n_mels=64,
                     f_min=0.0, f_max=None, power=2.0, ref_value=1.0,
                     amin=1e-10, top_db=None):
            self.mel = features.MelSpectrogram(sr, n_fft, hop_length, n_mels,
                                               f_min, f_max, power)
            self.ref = ref_value
            self.amin = amin
            self.top_db = top_db

        def __call__(self, x: Tensor):
            m = self.mel(x)

            def f(mv):
                db = 10.0 * jnp.log10(jnp.maximum(mv, self.amin))
                db = db - 10.0 * jnp.log10(jnp.maximum(self.ref, self.amin))
                if self.top_db is not None:
                    db = jnp.maximum(db, db.max() - self.top_db)
                return db

            return apply_op(f, m, name="log_mel")



# ---------------------------------------------------------------------------
# datasets (reference: python/paddle/audio/datasets — dataset.py base,
# esc50.py, tess.py). Zero-egress: with `files`/`labels` the datasets read
# real audio-feature arrays from disk (np.load-able); without, deterministic
# synthetic waveforms with the real label vocabulary + feature pipeline.

from paddle_tpu.io import Dataset as _IODataset  # noqa: E402


class AudioClassificationDataset(_IODataset):
    """Base: files + labels -> (feature, label) rows
    (reference audio/datasets/dataset.py:29)."""

    def __init__(self, files=None, labels=None, feat_type="raw",
                 sample_rate=16000, n_samples=128, n_classes=10, duration=1.0,
                 seed=0, **feat_kwargs):
        import numpy as _np

        self.feat_type = feat_type
        self.sample_rate = int(sample_rate)
        self.feat_kwargs = feat_kwargs
        if files is not None:
            self.files = list(files)
            self.labels = list(labels)
            self._synth = None
        else:
            rng = _np.random.RandomState(seed)
            n = int(self.sample_rate * duration)
            t = _np.arange(n) / self.sample_rate
            waves, labs = [], []
            for i in range(n_samples):
                lab = i % n_classes
                freq = 110.0 * (2.0 ** (lab / 2.0))
                w = _np.sin(2 * _np.pi * freq * t) + 0.05 * rng.randn(n)
                waves.append(w.astype(_np.float32))
                labs.append(lab)
            self.files = waves
            self.labels = labs
            self._synth = True

    def _waveform(self, idx):
        import numpy as _np

        item = self.files[idx]
        if isinstance(item, str):
            return _np.load(item).astype(_np.float32)
        return item

    def __getitem__(self, idx):
        import numpy as _np

        w = self._waveform(idx)
        if self.feat_type == "raw":
            feat = w
        elif self.feat_type == "mfcc":
            feat = _np.asarray(features.MFCC(
                sr=self.sample_rate, **self.feat_kwargs)(w)._value)
        elif self.feat_type == "melspectrogram":
            feat = _np.asarray(features.MelSpectrogram(
                sr=self.sample_rate, **self.feat_kwargs)(w)._value)
        elif self.feat_type == "logmelspectrogram":
            feat = _np.asarray(features.LogMelSpectrogram(
                sr=self.sample_rate, **self.feat_kwargs)(w)._value)
        else:
            raise ValueError(f"unknown feat_type {self.feat_type!r}")
        import numpy as _np2

        return feat, _np2.int64(self.labels[idx])

    def __len__(self):
        return len(self.files)


class ESC50(AudioClassificationDataset):
    """Environmental sounds, 50 classes x 5 folds
    (reference audio/datasets/esc50.py:26)."""

    label_list = [f"class_{i}" for i in range(50)]

    def __init__(self, mode="train", split=1, feat_type="raw", **kw):
        n_classes = 50
        super().__init__(feat_type=feat_type, n_classes=n_classes,
                         n_samples=200, seed=split, **kw)
        if self._synth:
            # fold `split` is the eval fold, as in the reference's 5-fold CSV
            idx = [i for i in range(len(self.files))
                   if (i % 5 == split - 1) == (mode != "train")]
            self.files = [self.files[i] for i in idx]
            self.labels = [self.labels[i] for i in idx]


class TESS(AudioClassificationDataset):
    """Emotional speech, 7 emotions (reference audio/datasets/tess.py)."""

    label_list = ["angry", "disgust", "fear", "happy", "neutral",
                  "pleasant_surprise", "sad"]

    def __init__(self, mode="train", n_folds=5, split=1, feat_type="raw", **kw):
        super().__init__(feat_type=feat_type, n_classes=7, n_samples=140,
                         seed=split, **kw)
        if self._synth:
            idx = [i for i in range(len(self.files))
                   if (i % n_folds == split - 1) == (mode != "train")]
            self.files = [self.files[i] for i in idx]
            self.labels = [self.labels[i] for i in idx]


__all__ += ["AudioClassificationDataset", "ESC50", "TESS"]

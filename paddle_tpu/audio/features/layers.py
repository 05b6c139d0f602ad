"""Audio feature layers (reference audio/features/layers.py: Spectrogram
:24, MelSpectrogram :106, LogMelSpectrogram :206, MFCC :309) built over
paddle.signal.stft + the functional mel/DCT helpers."""

from __future__ import annotations

from ...nn.layer.layers import Layer
from ...ops import manipulation as M
from ...ops import math as ops_math
from .. import functional as AF

__all__ = ["Spectrogram", "MelSpectrogram", "LogMelSpectrogram", "MFCC"]


class Spectrogram(Layer):
    def __init__(self, n_fft=512, hop_length=None, win_length=None,
                 window="hann", power=2.0, center=True, pad_mode="reflect",
                 dtype="float32"):
        super().__init__()
        self.n_fft = n_fft
        self.hop_length = hop_length if hop_length is not None else n_fft // 4
        self.win_length = win_length if win_length is not None else n_fft
        self.power = power
        self.center = center
        self.pad_mode = pad_mode
        w = AF.get_window(window, self.win_length, dtype=dtype)
        self.register_buffer("fft_window", w, persistable=False)

    def forward(self, x):
        from ... import signal

        spec = signal.stft(x, self.n_fft, hop_length=self.hop_length,
                           win_length=self.win_length,
                           window=self.fft_window, center=self.center,
                           pad_mode=self.pad_mode)
        mag = ops_math.abs(spec)
        if self.power == 2.0:
            return mag * mag
        if self.power != 1.0:
            return mag.pow(self.power)
        return mag


class MelSpectrogram(Layer):
    def __init__(self, sr=22050, n_fft=512, hop_length=None, win_length=None,
                 window="hann", power=2.0, center=True, pad_mode="reflect",
                 n_mels=64, f_min=50.0, f_max=None, htk=False, norm="slaney",
                 dtype="float32"):
        super().__init__()
        self._spectrogram = Spectrogram(n_fft, hop_length, win_length,
                                        window, power, center, pad_mode,
                                        dtype)
        self.n_mels = n_mels
        fbank = AF.compute_fbank_matrix(sr, n_fft, n_mels, f_min, f_max,
                                        htk, norm, dtype)
        self.register_buffer("fbank_matrix", fbank, persistable=False)

    def forward(self, x):
        spec = self._spectrogram(x)  # [..., n_freq, n_frames]
        return ops_math.matmul(self.fbank_matrix, spec)


class LogMelSpectrogram(Layer):
    def __init__(self, sr=22050, n_fft=512, hop_length=None, win_length=None,
                 window="hann", power=2.0, center=True, pad_mode="reflect",
                 n_mels=64, f_min=50.0, f_max=None, htk=False, norm="slaney",
                 ref_value=1.0, amin=1e-10, top_db=None, dtype="float32"):
        super().__init__()
        self._melspectrogram = MelSpectrogram(
            sr, n_fft, hop_length, win_length, window, power, center,
            pad_mode, n_mels, f_min, f_max, htk, norm, dtype)
        self.ref_value = ref_value
        self.amin = amin
        self.top_db = top_db

    def forward(self, x):
        mel = self._melspectrogram(x)
        return AF.power_to_db(mel, ref_value=self.ref_value, amin=self.amin,
                              top_db=self.top_db)


class MFCC(Layer):
    def __init__(self, sr=22050, n_mfcc=40, norm="ortho", dtype="float32",
                 **melkw):
        super().__init__()
        self._log_melspectrogram = LogMelSpectrogram(sr=sr, dtype=dtype,
                                                     **melkw)
        n_mels = self._log_melspectrogram._melspectrogram.n_mels
        assert n_mfcc <= n_mels, "n_mfcc cannot exceed n_mels"
        dct = AF.create_dct(n_mfcc, n_mels, norm, dtype)
        self.register_buffer("dct_matrix", dct, persistable=False)

    def forward(self, x):
        logmel = self._log_melspectrogram(x)  # [..., n_mels, n_frames]
        # [n_mels, n_mfcc]^T @ [..., n_mels, n_frames]
        return ops_math.matmul(M.transpose(self.dct_matrix, [1, 0]), logmel)

"""The TPU lowering of the M3TSZ decoder ends its loop with its streams
(PR 38). In a file of its own because `test_m3tsz_tpu.py` rides the
`slow` marker and these are tier-1: every served decode on the chip runs
this loop."""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from m3_tpu.encoding.m3tsz import Encoder, tpu  # noqa: E402
from m3_tpu.encoding.m3tsz import decode as scalar_decode  # noqa: E402
from m3_tpu.utils.xtime import TimeUnit  # noqa: E402

START = 1_600_000_000_000_000_000


class TestDecodeStopsAtTheEnd:
    """The TPU lowering's loop ends at the step where every live row has
    met its end of stream; what it writes is what the full-length scan
    of the CPU lowering writes."""

    @staticmethod
    def _words(rng, lengths, pad_rows):
        streams = []
        for n in lengths:
            enc = Encoder(START, int_optimized=False)
            t = START
            for _ in range(n):
                t += int(rng.integers(1, 30)) * 10**9
                enc.encode(t, float(rng.normal(50, 20)), TimeUnit.SECOND)
            streams.append(enc.stream())
        return streams, tpu.bytes_to_words(streams + [b""] * pad_rows, 64)

    @staticmethod
    def _steps_of(monkeypatch):
        """How many steps the (eagerly run) loop took."""
        from jax import lax

        taken = []

        def counting(cond, body, init):
            out = real(cond, body, init)
            taken.append(int(out[0]))
            return out
        real = lax.while_loop
        monkeypatch.setattr(tpu.lax, "while_loop", counting)
        return taken

    @pytest.mark.parametrize("lengths,pad_rows,n_live,steps", [
        ((40, 7, 23), 0, 3, 41),       # the longest row and its end marker
        ((40, 7, 23), 5, 3, 41),       # pad rows start out done
        ((40, 7, 23), 5, 8, 256),      # pad rows taken for streams never end
        ((1,), 3, 1, 2),
        ((12, 2), 2, 2, 13),
    ])
    def test_steps_and_parity_with_the_scan(self, rng, monkeypatch, lengths,
                                            pad_rows, n_live, steps):
        streams, words = self._words(rng, lengths, pad_rows)
        want = tpu._decode_gather(words, TimeUnit.SECOND, 256)
        taken = self._steps_of(monkeypatch)
        got = tpu._decode_shift(words, TimeUnit.SECOND, 256,
                                np.int32(n_live))
        assert taken == [steps]
        live = len(lengths)
        assert np.asarray(got.n_points)[:live].tolist() == list(lengths)
        for field in ("times", "value_bits", "valid", "n_points", "error"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, field))[:live],
                np.asarray(getattr(want, field))[:live], err_msg=field)
        if n_live == live:  # a pad row decodes to nothing
            assert not np.asarray(got.valid)[live:].any()
        for i, stream in enumerate(streams):
            dps = scalar_decode(stream, int_optimized=False)
            assert [d.timestamp_ns for d in dps] \
                == np.asarray(got.times)[i, :lengths[i]].tolist()

    def test_capacity_still_bounds_the_loop(self, rng, monkeypatch):
        streams, words = self._words(rng, (40,), 0)
        taken = self._steps_of(monkeypatch)
        got = tpu._decode_shift(words, TimeUnit.SECOND, 16, np.int32(1))
        assert taken == [16] and int(np.asarray(got.n_points)[0]) == 16

    def test_the_batch_path_marks_its_pad_rows(self, rng, monkeypatch):
        """hostpath pads the rows to a shape bucket and says how many
        are streams."""
        from m3_tpu.encoding.m3tsz import hostpath

        streams, _ = self._words(rng, (9, 30, 4), 0)
        seen = []
        real = tpu.decode

        def spying(words, unit, **kw):
            seen.append((words.shape[0], kw.get("n_live")))
            return real(words, unit, **kw)
        monkeypatch.setattr(tpu, "decode", spying)
        out = hostpath._decode_streams_device(streams, TimeUnit.SECOND, False)
        assert seen == [(3, 3)] or seen == [(4, 3)]
        assert [len(t) for t, _v in out] == [9, 30, 4]

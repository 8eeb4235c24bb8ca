"""Faults planted in the serving path, to see the check catch them: the
tests plant them at smoke size, ``bench/control.py --fault`` at a cell's
own size on the chip.  Each takes ``patch(obj, name, value)`` (pytest's
``monkeypatch.setattr``, or ``setattr``)."""
from __future__ import annotations


def token_altered(patch) -> None:
    """Every fifth token sampled is replaced by the next id."""
    from repro.serve import engine as engine_mod
    sample = engine_mod.ServeEngine._sample
    calls = {"n": 0}

    def altered(self, logits, req):
        tok = sample(self, logits, req)
        calls["n"] += 1
        return (tok + 1) % self.cfg.vocab_size if calls["n"] % 5 == 0 else tok

    patch(engine_mod.ServeEngine, "_sample", altered)


def state_unchanged(patch) -> None:
    """The decode step returns the cache it was given: no token's keys and
    values are written."""
    from repro.serve import engine as engine_mod
    decode = engine_mod.decode_step

    def unchanged(params, cfg, tokens, cache, pos, page_table=None):
        logits, _ = decode(params, cfg, tokens, cache, pos, page_table)
        return logits, cache

    patch(engine_mod, "decode_step", unchanged)


FAULTS = {"token_altered": token_altered, "state_unchanged": state_unchanged}

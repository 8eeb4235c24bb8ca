"""Share of the token rows that the window's bucketed prefills ran which
were padding: (slots x bucket - prompt tokens) / (slots x bucket), summed
over every prefill batch.  Moves ``tokens_per_s``."""


def read(run):
    rows = sum(p.rows for p in run.prefills)
    if not rows:
        return None
    return 100.0 * (rows - sum(p.tokens for p in run.prefills)) / rows

"""Program dispatch: share of the prefill programs' token positions that were
padding, 1 - real_tokens / padded_tokens over the context-encoding dispatches
of the window (deltas of the registry counters ``nxdi_real_tokens_total`` and
``nxdi_padded_tokens_total`` for that submodel). %."""

SUBMODEL = "context_encoding_model"


def read(run):
    real = run.counters.get(f"nxdi_real_tokens_total|{SUBMODEL}")
    padded = run.counters.get(f"nxdi_padded_tokens_total|{SUBMODEL}")
    if not padded:
        return None
    return 100.0 * (1.0 - real / padded)

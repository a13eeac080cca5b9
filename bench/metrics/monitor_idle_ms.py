"""Monitor: mean device-idle milliseconds per traced training step inside
``repro.talp.observe``, the TALP monitor's block on the step's outputs and
its load-balance sampling."""

import _spans


def read(ctx, name):
    return _spans.train_idle_ms(ctx, "observe")

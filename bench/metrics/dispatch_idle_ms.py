"""Train step: mean device-idle milliseconds per traced training step
inside ``repro.talp.train_step`` but outside its ``repro.talp.observe``:
the step's dispatch, up to the device's start of it."""

import _spans


def read(ctx, name):
    return _spans.train_idle_ms(ctx, "dispatch")

"""Device idle time inside ``engine.upload`` (the step's host-to-device
inputs), per ``engine.step`` span that decoded, in the traced window
(ms)."""
import spans


def read(run):
    return spans.idle_ms_per_decode_step(spans.of_run(run), "engine.upload")

"""Record the small trace that ``test_trace.py`` reduces, on a chip.

    python3 benchmarks/chip/tests/record_trace.py <out_dir>

Runs a few ``serve_step`` calls of a small two-layer dense model through
the program's ``DecodeEngine`` inside a ``bench:window`` span, with
``bench:prefill`` and ``bench:decode`` spans and one host sleep between
them that leaves the device idle, and writes the trace, a serialized
XSpace, to ``<out_dir>/small.xplane.pb``.  The test needs the steps' count
(``STEPS``) and the sleep (``SLEEP_S``).
"""

import dataclasses
import sys
import time
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

PROMPT, NEW = 4, 5
STEPS = PROMPT + NEW - 1
SLEEP_S = 0.05


def main(out_dir: str) -> None:
    import jax
    import numpy as np

    import trace as trace_mod
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import DecodeEngine

    assert jax.devices()[0].platform == "tpu", "record the trace on a chip"
    cfg = dataclasses.replace(get_config("h2o-danube-1.8b"), n_layers=2, vocab_size=2048)
    api = build_model(cfg)
    params = jax.jit(api.init)(jax.random.PRNGKey(0))
    engine = DecodeEngine(api, params, batch=8, max_len=256)
    warm = DecodeEngine(api, params, batch=8, max_len=256)
    np.asarray(warm.generate(np.zeros((8, 1), np.int32), 2))
    span = lambda n: jax.profiler.TraceAnnotation(trace_mod.SPAN_PREFIX + n)  # noqa: E731
    session = trace_mod.start()
    with span("window"):
        with span("prefill"):
            first = engine.prefill(np.ones((8, PROMPT), np.int32))
            np.asarray(first)
        with span("sleep"):
            time.sleep(SLEEP_S)
        with span("decode"):
            np.asarray(engine.generate(first, NEW - 1))
    xspace = session.stop()
    Path(out_dir).mkdir(parents=True, exist_ok=True)
    (Path(out_dir) / "small.xplane.pb").write_bytes(xspace)
    print(trace_mod.summarize(str(Path(out_dir) / "small.xplane.pb")))


if __name__ == "__main__":
    main(sys.argv[1])

"""Metrics, one reader per file, found by the metric's name:
`bench/metrics/<name>.py` defines `read(ctx)`, which returns the metric's
value, or None where the run has nothing to read (the harness then leaves
the metric out of the result line). `ctx` is `bench.run.Context`: the run's
record (`bench.drive.Record`), the model's sizes, the quantization, the
engine settings, the chip's peaks, `setup_s`, in a traced run the reduced
trace (`bench.trace.reduce`), and the configuration's architecture
(`bench/arch/<arch>/`), through which a reader that needs a shape gets
it."""

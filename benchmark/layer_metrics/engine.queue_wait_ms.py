"""95th percentile, milliseconds, of `serve.queued`: a request's wait from
entering the queue (submission, or a requeue after preemption) to the
scheduler's admission stamp, over the admissions inside the window."""
from benchmark.harness import program_spans


def read(run):
    return program_spans.ending_in_window_ms(run, "serve.queued", 95)

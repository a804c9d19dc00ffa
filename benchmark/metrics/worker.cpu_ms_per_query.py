"""CPU time the kernel charged to the threads that serve queries (the
``tsd-query`` workers and the ``tsd-subq`` fan-out) a served query
(``tsd.runtime.thread_cpu_ms``): beside the ``query.http`` stage's
mean, how long a request's threads did not run."""
import envreaders


def read(ctx):
    return envreaders.serving_cpu_ms_per_query(ctx)

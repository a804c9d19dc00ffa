"""Device time of the rollup-average program a request: the executions
in the traced stretch of the compiled programs named
``run_pipeline_avg_div`` (the division of the SUM grid by the COUNT
grid and the shared tail: interpolation, group sum), their mean."""
import rollupreaders


def read(ctx):
    n, secs = rollupreaders.program_modules(ctx)
    return secs * 1000.0 / n if n else None

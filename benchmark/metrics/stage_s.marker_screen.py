"""Mean seconds per job in the program's `marker-screen` stage (host clock, from
each job's run report; the stage ends once its results are on the
host)."""

from readers import mean_stage_seconds


def read(ctx):
    return mean_stage_seconds(ctx, "marker-screen")

"""95th percentile of the explain ``queue`` stage (admitted to drained into
a wave by ``StreamingAdmission``) over the window's answered statements."""
from bench import stats


def read(run):
    xs = run.stage("queue")
    return stats.percentile(xs, 95) if xs else None

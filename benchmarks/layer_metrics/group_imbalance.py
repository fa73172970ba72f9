"""grouped outer loop: the fullest group over the mean, less one, after
the displacement and its ``fix_contiguity`` (``largest`` and ``mean`` of
the job's ``grp displace`` span): the cut the second pass runs on.  The
front advances INTO the smaller group, so the groups next to a seam that
moved far fill up, and the capacity follows the fullest (ROADMAP B11).
None where the job displaced nothing or the program's span carries no
such fields."""
from span_fields import last_job_spans


def read(run):
    moves = last_job_spans("grp displace")
    if not moves or not moves[0].get("mean") \
            or moves[0].get("largest") is None:
        return None
    return 100.0 * (moves[0]["largest"] / moves[0]["mean"] - 1.0)

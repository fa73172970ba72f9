"""grouped outer loop: of the tets the second pass splits (``mean`` of
the job's ``grp displace`` span times the ``groups`` of its ``grp
split``), the share whose group the displacement between the passes
changed (``moved``: the advancing front and the ``fix_contiguity`` after
it).  None where the job displaced nothing or the program's span
carries no such fields."""
from span_fields import last_job_spans


def read(run):
    moves = last_job_spans("grp displace")
    splits = last_job_spans("grp split")
    if not moves or not splits or moves[0].get("moved") is None \
            or not moves[0].get("mean") or not splits[-1].get("groups"):
        return None
    return 100.0 * moves[0]["moved"] / (moves[0]["mean"]
                                        * splits[-1]["groups"])

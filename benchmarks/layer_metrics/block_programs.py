"""entry: executables of the grouped cycle block (ledger entry
``groups.adapt_block``) this process built or took from the persistent
cache, counter ``compile.block_programs``: a process total, set-up and
window together.  One a job shape is what the program needs; each is
minutes of compile cold and about 115 MB read, decompressed and loaded
warm, so a second one at the same shapes shows in ``setup_s``."""
from job import program_counters


def read(run):
    return program_counters().get("compile.block_programs")

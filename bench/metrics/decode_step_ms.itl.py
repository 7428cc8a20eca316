"""Device time of one paged decode program execution, averaged over the
traced window."""
import readers

PROGRAM = r"decode_step_paged"
read = readers.program_ms(PROGRAM)

"""Device time of one chunked-prefill program execution, averaged over the
traced window.  A chunk runs in most iterations beside the decode, so it
sets the gap between tokens."""
import readers

PROGRAM = r"prefill_chunk"
read = readers.program_ms(PROGRAM)

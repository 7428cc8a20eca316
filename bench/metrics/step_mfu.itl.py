"""Whole-iteration model FLOP utilisation: useful tokens' model
operations over the window's iterations' wall time times the bf16 peak."""
import readers

read = readers.step_mfu

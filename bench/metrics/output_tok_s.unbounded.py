"""``output_tok_s`` read per layer, with no bound: the end-to-end rate
of a cell whose runs swing from process to process more widely than
any bound the benchmark may set."""
from bench.lookup import load_reader


def read(run):
    return load_reader("output_tok_s").read(run)

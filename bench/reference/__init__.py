"""Plain references the benchmark compares the timed path with.  They
import nothing of the program."""

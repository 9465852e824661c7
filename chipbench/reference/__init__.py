"""The benchmark's plain reference: its own Siddon tracer, phantom and
float64 CGNR.  Nothing here imports the program under test, so the
yardstick cannot move with it."""

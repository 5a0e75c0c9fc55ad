"""The benchmark's plain reference: the scalar campaign simulator, frozen.

A copy of the event-driven ``ClusterSim`` (failure injection, gang
scheduling, retry chains, checkpoints, storage fabric, and the control
plane with its numpy detector) and of the findings fold, as they stood
when the benchmark was defined.  It imports nothing of the program
under test, so a later change to the program cannot move the yardstick
that decides ``correct``.  Only the numpy detector backend exists here.
"""

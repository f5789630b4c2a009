"""The benchmark's own tests: the control and the faults that ``correct``
has to catch, at a size a test run can hold, and what ``selfcheck.py``
rehearses on the CPU. Not part of the repo's tier-1 tests."""

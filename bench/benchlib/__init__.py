"""The HFEL benchmark's own library: specs, traffic generation, the
sample of answers, the plain reference, trace reduction and the peak table.

Nothing here is imported by the program under test. The program is driven
by the request loops under ``bench/loops/``; of its modules the library
imports only the ``Scenario`` type it builds, the compile cache and the
compile log.
"""

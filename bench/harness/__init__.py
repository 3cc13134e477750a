"""The benchmark's own code: data and traffic generators, the open-loop
client, the plain references, the trace reduction and one driver per
operation (``allpairs``, ``serve``). Nothing here is imported by the
program; the drivers import the program as the system under test."""

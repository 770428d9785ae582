"""The session engine's block kinds (``models/seqrec.BLOCKS``), a module
each; what two or more of them share is in ``common``. No module here
imports ``models.seqrec`` or ``templates``."""
